"""Distributed whole-step capture (ISSUE 13): AMP/GradScaler steps and
DistTrainStep run through the SOT capture engine, with bucketed
compute–collective overlap.

Pins:

- an AMP/GradScaler ``Model.fit``-style step runs as ONE donated
  captured executable: the dynamic audit reports ZERO host syncs and
  exactly one executable call in steady state, and
  ``sot.fallbacks_total{reason=amp}`` stays 0 (the PR 10 residue,
  asserted extinct — the reason label no longer exists);
- captured-vs-eager equality for AMP steps, including a forced
  non-finite skip step: the scaler plane (scale value, good/bad
  counters, skip decision) is BIT-equal, loss/params equal at the
  f32-ulp fusion-rounding bound the PR 10 kill-switch test pinned
  (per-op eager XLA vs one whole program round differently in the
  last bit; bf16 autocast widens that to bf16 epsilon);
- ``DistTrainStep`` routes through ``CapturedStep`` (its bespoke
  ``jax.jit`` closure is GONE): shared compile/cache-hit counters,
  signature-change retrace, checkpoint restore -> continue identical
  under both kill-switch settings;
- bucketed gradient sync: assignment unit laws (every grad in exactly
  one bucket, reverse-backward order preserved, byte target
  respected), the captured distributed program carries >= 2 buckets
  whose collectives are pinned in the jaxpr (optimization_barrier
  chain + sharding_constraint nodes) and the HLO, the FIRST bucket's
  sync depends on only a fraction of the backward's dot_generals
  (the DAG independence that lets XLA's async collectives overlap
  remaining backward compute — the T3 structure), per-bucket flight
  events journal each step, and bucketing on/off is numerically
  identical.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import analysis
from paddle_tpu.hapi import Model
from paddle_tpu.observability import flight
from paddle_tpu.observability import metrics as om


def _toy_data(n=32, din=4, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, din)).astype(np.float32)
    W = rng.normal(size=(din, classes)).astype(np.float32)
    y = (X @ W).argmax(-1).astype(np.int64)
    return X, y


def _amp_model(**scaler_kw):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Linear(16, 3))
    m = Model(net)
    m.prepare(optimizer=paddle.optimizer.Adam(
        learning_rate=0.01, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss(),
        amp_configs={"level": "O1", "init_loss_scaling": 1024.0,
                     **scaler_kw})
    return m


class TestAmpCapture:
    def test_amp_step_captures_with_zero_fallbacks(self):
        X, y = _toy_data()
        m = _amp_model()
        for i in range(6):
            sl = slice((i * 8) % 32, (i * 8) % 32 + 8)
            float(m.train_batch([X[sl]], [y[sl]])[0])
        eng = m._captured
        # strict compile policy: sighting -> compile -> hits
        assert eng.stats["eager_steps"] == 1
        assert eng.stats["compiles"] == 1
        assert eng.stats["captured_steps"] == 5
        assert eng.stats["fallbacks"] == {}, eng.stats
        # the PR 10 residue is EXTINCT: no amp fallback reason exists
        cell = om.default_registry().get("sot.fallbacks_total")
        assert cell.value(reason="amp") == 0

    def test_captured_amp_step_audits_dispatch_free(self):
        """The acceptance pin: a steady-state AMP/GradScaler train
        step is ONE executable call with ZERO host syncs — the skip
        decision, the scale bookkeeping and the loss all stay on
        device (the loss fetches at the log boundary)."""
        X, y = _toy_data()
        m = _amp_model()
        for _ in range(3):
            m.train_batch([X[:8]], [y[:8]])

        def step():
            m.train_batch([X[:8]], [y[:8]])

        rep = analysis.audit(step, warmup=2)
        assert rep.syncs == [], rep.syncs
        before = dict(om.snapshot().get("sot", {}))
        m.train_batch([X[:8]], [y[:8]])
        after = dict(om.snapshot().get("sot", {}))
        assert after["captured_steps_total"] - \
            before["captured_steps_total"] == 1

    def test_captured_matches_eager_with_nonfinite_skip(self):
        """Captured vs FLAGS_sot_capture=0 eager, same 7-step stream
        with one poisoned batch at step 4: the scaler plane is
        BIT-equal (scale halves exactly once, the poisoned update is
        skipped on both paths), losses/weights agree at the bf16
        fusion-rounding bound."""
        X, y = _toy_data()
        X_bad = X[:8].copy()
        X_bad[0, 0] = np.inf

        def run(m):
            scales, losses, snaps = [], [], []
            for i in range(7):
                xb = X_bad if i == 4 else X[(i * 8) % 32:
                                            (i * 8) % 32 + 8]
                yb = y[:8] if i == 4 else y[(i * 8) % 32:
                                            (i * 8) % 32 + 8]
                losses.append(float(m.train_batch([xb], [yb])[0]))
                scales.append(float(m._scaler.get_loss_scaling()))
                snaps.append(m.network[0].weight.numpy().copy())
            return scales, losses, snaps

        m_cap = _amp_model(decr_every_n_nan_or_inf=1)
        s_cap, l_cap, w_cap = run(m_cap)
        assert m_cap._captured.stats["fallbacks"] == {}
        assert m_cap._captured.stats["captured_steps"] >= 5
        # the poisoned step: update skipped, scale halved (bit-exact —
        # powers of two), training resumes on the next step
        assert s_cap[3] == 1024.0 and s_cap[4] == 512.0, s_cap
        np.testing.assert_array_equal(w_cap[4], w_cap[3])
        assert not np.array_equal(w_cap[5], w_cap[4])

        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            m_off = _amp_model(decr_every_n_nan_or_inf=1)
            s_off, l_off, w_off = run(m_off)
            assert m_off._captured.stats["captured_steps"] == 0
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})
        # scaler state: bit-equal across the whole stream
        np.testing.assert_array_equal(np.array(s_cap), np.array(s_off))
        np.testing.assert_allclose(np.array(l_cap), np.array(l_off),
                                   rtol=2e-3)
        np.testing.assert_allclose(w_cap[-1], w_off[-1], rtol=2e-3,
                                   atol=1e-4)

    def test_f32_amp_matches_eager_at_ulp(self):
        """With matmul/linear black-listed (pure-f32 numerics) the
        captured scaler iteration reproduces eager at the same
        one-ulp bound the plain captured step has — the scaler
        fold-in itself adds NOTHING."""
        X, y = _toy_data()

        def build():
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(4, 16), nn.Tanh(),
                                nn.Linear(16, 3))
            m = Model(net)
            m.prepare(optimizer=paddle.optimizer.Adam(
                learning_rate=0.01, parameters=net.parameters()),
                loss=nn.CrossEntropyLoss(),
                amp_configs={"level": "O1",
                             "init_loss_scaling": 1024.0,
                             "custom_black_list": ["matmul", "linear"]})
            return m

        def run(m):
            return [float(m.train_batch(
                [X[(i * 8) % 32:(i * 8) % 32 + 8]],
                [y[(i * 8) % 32:(i * 8) % 32 + 8]])[0])
                for i in range(6)]

        caps = run(build())
        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            offs = run(build())
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})
        np.testing.assert_allclose(caps, offs, rtol=1e-6, atol=1e-7)

    def test_custom_scaler_step_falls_back_counted(self):
        """An instance-patched scaler (the shard_scaler wrap pattern)
        cannot capture: the step falls back eagerly with a counted
        ``scaler`` reason and the patched hook actually runs."""
        X, y = _toy_data()
        m = _amp_model()
        calls = []
        orig = m._scaler.unscale_
        m._scaler.unscale_ = lambda o: (calls.append(1), orig(o))[1]
        for _ in range(3):
            float(m.train_batch([X[:8]], [y[:8]])[0])
        assert calls, "the patched unscale_ must run (eager path)"
        assert m._captured.stats["fallbacks"].get("scaler", 0) >= 1
        assert m._captured.stats["captured_steps"] == 0


@pytest.fixture
def fsdp_llama():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.distributed.dist_train import DistTrainStep
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion,
                                   shard_llama)

    mesh = ProcessMesh(np.arange(8), dim_names=["fsdp"])
    crit = LlamaPretrainingCriterion()

    def build(seed=0, **kw):
        paddle.seed(seed)
        # as small as a sharded llama gets: the file's cost is the
        # 8-virtual-device SPMD steps (every ZeRO-3 param pays
        # all-gather + reduce-scatter rendezvous per step, ~20ms each
        # on the single-core CI host), and tier-1 has an 870s budget —
        # ONE hidden layer keeps the collective count down while still
        # giving >= 2 grad buckets and a multi-dot backward
        cfg = LlamaConfig.tiny(
            num_hidden_layers=1, hidden_size=16, intermediate_size=32,
            num_attention_heads=2, num_key_value_heads=2,
            vocab_size=64, use_flash_attention=False)
        m = LlamaForCausalLM(cfg)
        shard_llama(m, mesh, tp_axis=None, fsdp_axis="fsdp")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = DistTrainStep(
            m, lambda lg, lb: crit(lg, lb), opt,
            data_sharding=NamedSharding(mesh.to_jax_mesh(),
                                        P("fsdp", None)), **kw)
        return m, step

    ids = np.random.default_rng(0).integers(
        0, 64, (8, 16)).astype(np.int32)
    return build, ids


class TestDistCapturedStep:
    def test_dist_step_routes_through_captured_step(self, fsdp_llama):
        from paddle_tpu.jit.sot import CapturedStep
        build, ids = fsdp_llama
        _, step = build()
        # the bespoke jax.jit closure is GONE: the engine IS a
        # CapturedStep (non-strict), sharing guards/cache/telemetry
        assert isinstance(step._step, CapturedStep)
        assert not hasattr(step, "_jitted")
        before = dict(om.snapshot().get("sot", {}))
        losses = [float(step(ids, ids)) for _ in range(3)]
        after = dict(om.snapshot().get("sot", {}))
        assert step.stats["compiles"] == 1
        assert step.stats["captured_steps"] == 3
        assert step.stats["cache_hits"] == 2
        assert after["captured_steps_total"] - \
            before["captured_steps_total"] == 3
        assert losses[-1] < losses[0] + 1.0
        # signature-change retrace on the SAME engine: a new batch
        # shape is a guard miss — retrace, old program stays cached
        float(step(ids[:, :8], ids[:, :8]))
        assert step.stats["compiles"] == 2
        hits = step.stats["cache_hits"]
        float(step(ids, ids))           # first signature still serves
        assert step.stats["compiles"] == 2
        assert step.stats["cache_hits"] == hits + 1

    def test_checkpoint_restore_continue_both_flag_settings(
            self, fsdp_llama, tmp_path):
        """Train 2 steps, checkpoint through the shared optimizer
        state plane, rebuild, restore, continue — the loss stream
        matches the straight-through run under BOTH kill-switch
        settings (DistTrainStep is an explicit whole-step API like
        TrainStep: the kill switch does not change its path, and the
        stream must prove it)."""
        build, ids = fsdp_llama
        import paddle_tpu.distributed as dist
        # ONE stream is both the checkpoint source and the reference
        # (DistTrainStep is an explicit whole-step API — the kill
        # switch does not change its path, so the streams must agree
        # across flag settings too): train 2 steps, save, keep
        # training — the post-save tail is what each restore leg must
        # reproduce
        m1, step1 = build(seed=7)
        [float(step1(ids, ids)) for _ in range(2)]
        dist.save_state_dict(
            {"model": m1.state_dict(), "opt": step1.state_dict()},
            str(tmp_path / "ck"))
        ref = [float(step1(ids, ids)) for _ in range(2)]
        for flag in (1, 0):
            paddle.set_flags({"FLAGS_sot_capture": flag})
            try:
                m2, step2 = build(seed=7)
                opt_sd = step2.state_dict()
                dist.load_state_dict(
                    {"model": m2.state_dict(), "opt": opt_sd},
                    str(tmp_path / "ck"))
                step2.set_state_dict(opt_sd)
                l2 = [float(step2(ids, ids)) for _ in range(2)]
                np.testing.assert_allclose(l2, ref, rtol=2e-4,
                                           err_msg=f"flag={flag}")
            finally:
                paddle.set_flags({"FLAGS_sot_capture": 1})


class TestBucketAssignment:
    def test_every_grad_in_exactly_one_bucket_order_preserved(self):
        from paddle_tpu.distributed.collective import bucket_assignment
        sizes = [(f"g{i}", 100) for i in range(10)]
        buckets = bucket_assignment(sizes, 250)
        flat = [k for b in buckets for k in b]
        assert flat == [k for k, _ in sizes]          # order preserved
        assert len(flat) == len(set(flat)) == 10      # exactly once
        # byte target respected: no bucket exceeds it unless a single
        # grad alone does
        for b in buckets:
            total = sum(100 for _ in b)
            assert total <= 250 or len(b) == 1

    def test_oversized_grad_gets_its_own_bucket(self):
        from paddle_tpu.distributed.collective import bucket_assignment
        sizes = [("a", 10), ("big", 1000), ("b", 10), ("c", 10)]
        buckets = bucket_assignment(sizes, 100)
        assert ["big"] in buckets
        flat = [k for b in buckets for k in b]
        assert flat == ["a", "big", "b", "c"]

    def test_disabled_target_single_bucket(self):
        from paddle_tpu.distributed.collective import bucket_assignment
        sizes = [("a", 10), ("b", 10)]
        assert bucket_assignment(sizes, 0) == [["a", "b"]]
        assert bucket_assignment([], 0) == []
        assert bucket_assignment([], 100) == []

class TestBucketedOverlapProgram:
    class _flag:
        """Hold FLAGS_dist_grad_bucket_bytes for a block: the target
        is a signature guard, so measurement must run under the same
        value the program was traced with."""

        def __init__(self, value):
            self.value = value

        def __enter__(self):
            self.prev = paddle.get_flags("FLAGS_dist_grad_bucket_bytes")
            paddle.set_flags(
                {"FLAGS_dist_grad_bucket_bytes": self.value})

        def __exit__(self, *exc):
            paddle.set_flags(self.prev)
            return False

    def test_program_structure_pinned(self, fsdp_llama):
        """The captured distributed program carries >= 2 gradient
        buckets as first-class nodes: vs the flag=0 epilogue program
        the jaxpr grows exactly (n_buckets - 1) optimization_barriers
        (the issue-order chain) and one sharding_constraint per
        bucketed grad; the compiled HLO carries >= 2 collective
        sites; and the FIRST bucket's sync transitively depends on
        only a fraction of the backward's dot_generals while the
        LAST depends on (almost) all — the DAG independence that
        lets async collectives overlap remaining backward compute."""
        import re
        import jax.extend.core as jcore
        build, ids = fsdp_llama

        prev = paddle.get_flags("FLAGS_dist_grad_bucket_bytes")
        try:
            paddle.set_flags({"FLAGS_dist_grad_bucket_bytes": 2048})
            _, step_on = build()
            l_on = [float(step_on(ids, ids)) for _ in range(2)]
            plan = step_on.bucket_plan()
            assert len(plan) >= 2, plan
            jx_on = step_on.trace_jaxpr(ids, ids).jaxpr
            paddle.set_flags({"FLAGS_dist_grad_bucket_bytes": 0})
            _, step_off = build()
            l_off = [float(step_off(ids, ids)) for _ in range(2)]
            assert step_off.bucket_plan() == []
            jx_off = step_off.trace_jaxpr(ids, ids).jaxpr
        finally:
            paddle.set_flags(prev)
        # bucketing is semantically inert: the sync nodes materialize
        # the SAME reduced grads the epilogue program computes
        np.testing.assert_allclose(l_on, l_off, rtol=1e-6)

        def count(jaxpr, name):
            return sum(1 for e in jaxpr.eqns
                       if e.primitive.name == name)

        n_grads = sum(b["grads"] for b in plan)
        assert count(jx_on, "optimization_barrier") - \
            count(jx_off, "optimization_barrier") == len(plan) - 1
        assert count(jx_on, "sharding_constraint") - \
            count(jx_off, "sharding_constraint") == n_grads

        # HLO: the partitioner landed real collectives per bucket
        prev2 = paddle.get_flags("FLAGS_dist_grad_bucket_bytes")
        paddle.set_flags({"FLAGS_dist_grad_bucket_bytes": 2048})
        try:
            _, compiled, _ = step_on.compile_stats(
                ids, ids, return_compiled=True)
        finally:
            paddle.set_flags(prev2)
        n_coll = len(re.findall(r"(all-reduce|reduce-scatter)\(",
                                compiled.as_text()))
        assert n_coll >= 2, n_coll

        # dependency pin: walk the jaxpr DAG from each bucket sync
        eqns = jx_on.eqns
        prod = {}
        for i, e in enumerate(eqns):
            for ov in e.outvars:
                prod[id(ov)] = i
        dots = {i for i, e in enumerate(eqns)
                if e.primitive.name == "dot_general"}

        def dot_deps(i):
            seen, stack = set(), [i]
            while stack:
                j = stack.pop()
                if j in seen:
                    continue
                seen.add(j)
                for iv in eqns[j].invars:
                    if isinstance(iv, jcore.Literal):
                        continue
                    p = prod.get(id(iv))
                    if p is not None:
                        stack.append(p)
            return len(seen & dots)

        wsc = [i for i, e in enumerate(eqns)
               if e.primitive.name == "sharding_constraint"]
        # bucket syncs trace AFTER the forward's constraints: the last
        # n_grads sharding_constraint eqns are the bucket nodes, in
        # bucket issue order
        bucket_wsc = wsc[-n_grads:]
        first_deps = dot_deps(bucket_wsc[0])
        last_deps = dot_deps(bucket_wsc[-1])
        assert first_deps < last_deps, (first_deps, last_deps)
        # the first bucket must NOT need the whole backward — that
        # independence is the overlap window
        assert first_deps <= 0.7 * len(dots), (first_deps, len(dots))

    def test_per_bucket_flight_events_each_step(self, fsdp_llama):
        build, ids = fsdp_llama
        with self._flag(2048):
            m, step = build()
            float(step(ids, ids))
            plan = step.bucket_plan()
            assert len(plan) >= 2
            # the plan walks grads in REVERSE registration (forward)
            # order — the last layers' grads, which backward retires
            # first, land in the first buckets — each exactly once
            flat = [k for b in plan for k in b["keys"]]
            reg_order = [k for k, p in m.named_parameters()
                         if not p.stop_gradient]
            assert flat == list(reversed(reg_order))
            flight.clear()
            float(step(ids, ids))
        ev = [e for e in flight.events(category="collective")
              if e["name"] == "grad_bucket"]
        assert len(ev) == len(plan), (len(ev), len(plan))
        assert [e["attrs"]["bytes"] for e in ev] == \
            [b["bytes"] for b in plan]
        summary = [e for e in flight.events(category="collective")
                   if e["name"] == "dist_step"]
        assert summary and \
            summary[-1]["attrs"]["buckets"] == len(plan)
        assert summary[-1]["attrs"]["dur_us"] > 0
        # flag round-trip onto CACHED programs: plans are keyed per
        # (bucket_bytes, trainable set), so an epilogue replay reports
        # no buckets and journals nothing, and flipping back restores
        # THIS program's plan — no retrace, no phantom telemetry
        with self._flag(0):
            float(step(ids, ids))            # traces the epilogue once
            flight.clear()
            float(step(ids, ids))            # cached epilogue replay
            assert step.bucket_plan() == []
            assert not [e for e in flight.events(category="collective")
                        if e["name"] == "grad_bucket"]
        with self._flag(2048):
            flight.clear()
            float(step(ids, ids))            # cached bucketed replay
            assert step.bucket_plan() == plan
            assert len([e for e in flight.events(category="collective")
                        if e["name"] == "grad_bucket"]) == len(plan)
        assert step.stats["compiles"] == 2   # one per flag value
