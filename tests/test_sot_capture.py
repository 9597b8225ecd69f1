"""Fusion III — whole-step program capture (ISSUE 10).

The SOT plane (jit/sot.py) executes the capture plan PR 7 proved
CONSISTENT: hapi.Model train/eval batches and jit.TrainStep run as ONE
cached, buffer-donated executable (CapturedStep); SOTFunction replays
recorded paths through lazily-compiled segments with speculatively
validated guards; every unreplayable event falls back to per-chain
eager fusion with a counted reason. Pinned here:

- guard miss -> discard-speculated-tail -> retrace is bit-identical to
  eager, and counted (sot.guard_misses_total / retraces_total);
- captured training -> CheckpointManager restore -> continue matches
  the uncaptured (FLAGS_sot_capture=0) run;
- held ``p.detach()`` snapshots survive donated captured steps (the
  PR 5 alias-registry contract, now under SOT);
- fallbacks are total, counted by reason, and flight-journaled;
- BucketPolicy bounds the captured-executable set for varlen batches.

(The llama acceptance — audit-asserted zero syncs / <= a handful of
flushes / <= 3 executables inside a captured ``Model.fit`` step — lives
in tests/test_capture_plan.py::test_captured_fit_step_runs_dispatch_free
next to the planner contract it closes.)
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.hapi import Model
from paddle_tpu.jit.sot import BucketPolicy, CapturedStep, SOTFunction
from paddle_tpu.observability import flight
from paddle_tpu.observability import metrics as om


def _sot_snap():
    return dict(om.snapshot().get("sot", {}))


def _toy_data(n=32, din=4, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, din)).astype(np.float32)
    W = rng.normal(size=(din, classes)).astype(np.float32)
    y = (X @ W).argmax(-1).astype(np.int64)
    return X, y


def _model(lr=0.01, seed=0):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Linear(16, 3))
    m = Model(net)
    m.prepare(optimizer=paddle.optimizer.Adam(
        learning_rate=lr, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    return m


def _run_steps(m, X, y, steps, bs=8, start=0):
    losses = []
    for i in range(start, start + steps):
        sl = slice((i * bs) % len(X), (i * bs) % len(X) + bs)
        loss = m.train_batch([X[sl]], [y[sl]])
        losses.append(float(loss[0]))  # the log boundary fetch
    return losses


def _total(v):
    """A labeled counter snapshots as {label: n}; unlabeled as n."""
    return sum(v.values()) if isinstance(v, dict) else v


class TestCapturedTraining:
    def test_steady_state_is_one_executable(self):
        X, y = _toy_data()
        m = _model()
        before = _sot_snap()
        losses = _run_steps(m, X, y, 8)
        after = _sot_snap()
        eng = m._captured
        # compile policy: sighting -> compile -> hits (one signature)
        assert eng.stats["eager_steps"] == 1
        assert eng.stats["compiles"] == 1
        assert eng.stats["cache_hits"] == 6
        assert eng.stats["captured_steps"] == 7
        assert after["captured_steps_total"] - \
            before["captured_steps_total"] == 7
        assert eng.stats["fallbacks"] == {}
        assert losses[-1] < losses[0], losses

    def test_lazy_loss_is_a_device_tensor(self):
        X, y = _toy_data()
        m = _model()
        out = m.train_batch([X[:8]], [y[:8]])
        from paddle_tpu.core.tensor import Tensor
        assert isinstance(out[0], Tensor)
        assert float(out[0]) > 0  # fetch works at the boundary

    def test_kill_switch_restores_eager_path(self):
        X, y = _toy_data()
        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            m_off = _model()
            off = _run_steps(m_off, X, y, 6)
            assert m_off._captured.stats["captured_steps"] == 0
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})
        m_on = _model()
        on = _run_steps(m_on, X, y, 6)
        assert m_on._captured.stats["captured_steps"] >= 4
        np.testing.assert_allclose(on, off, rtol=1e-5, atol=1e-6)
        for (k, p_on), p_off in zip(
                m_on.network.state_dict().items(),
                m_off.network.state_dict().values()):
            np.testing.assert_allclose(
                p_on.numpy(), p_off.numpy(), rtol=1e-5, atol=1e-6,
                err_msg=k)

    def test_checkpoint_restore_continue_matches_uncaptured(self,
                                                           tmp_path):
        from paddle_tpu.framework.checkpoint import CheckpointManager
        X, y = _toy_data()
        # reference: 6 uncaptured steps straight through
        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            m_ref = _model()
            _run_steps(m_ref, X, y, 6)
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})
        # captured: 3 steps -> checkpoint -> restore -> 3 more
        m1 = _model()
        _run_steps(m1, X, y, 3)
        cm = CheckpointManager(str(tmp_path))
        cm.save({"net": {k: paddle.to_tensor(v.numpy()) for k, v in
                         m1.network.state_dict().items()},
                 "opt": m1._optimizer.state_dict()}, step=3)
        del m1
        step, ckpt = cm.restore()
        assert step == 3
        m2 = _model()
        m2.network.set_state_dict(ckpt["net"])
        m2._optimizer.set_state_dict(ckpt["opt"])
        _run_steps(m2, X, y, 3, start=3)  # steps 4-6 resume mid-stream
        for (k, got), ref in zip(m2.network.state_dict().items(),
                                 m_ref.network.state_dict().values()):
            np.testing.assert_allclose(
                got.numpy(), ref.numpy(), rtol=1e-5, atol=1e-6,
                err_msg=k)

    def test_detach_snapshot_survives_donated_steps(self):
        X, y = _toy_data()
        m = _model()
        _run_steps(m, X, y, 3)  # warm: the next step is captured
        p = m.network[0].weight
        snap = p.detach()
        frozen = np.asarray(snap.numpy()).copy()
        _run_steps(m, X, y, 2)  # donating captured steps
        # the live param moved; the held snapshot did not (and its
        # buffer was not deleted under it by the donation)
        assert not np.allclose(p.numpy(), frozen)
        np.testing.assert_array_equal(snap.numpy(), frozen)

    def test_primed_grads_fall_back_and_accumulate(self):
        X, y = _toy_data()
        m = _model()
        _run_steps(m, X, y, 3)
        p = m.network[0].weight
        p.grad = paddle.to_tensor(np.ones(p.shape, np.float32))
        m.train_batch([X[:8]], [y[:8]])  # must take the eager path
        assert m._captured.stats["fallbacks"].get("pending_grads", 0) \
            >= 1

    def test_forward_hook_falls_back(self):
        X, y = _toy_data()
        m = _model()
        _run_steps(m, X, y, 3)
        seen = []
        h = m.network[0].register_forward_post_hook(
            lambda lyr, i, o: seen.append(1))
        try:
            m.train_batch([X[:8]], [y[:8]])
        finally:
            h.remove()
        assert seen, "the hook must actually run (eager path)"
        assert m._captured.stats["fallbacks"].get("hooks", 0) >= 1
        # hook removed: capture resumes on the cached program
        before = m._captured.stats["captured_steps"]
        m.train_batch([X[:8]], [y[:8]])
        assert m._captured.stats["captured_steps"] == before + 1

    def test_eval_capture_matches_eager(self):
        X, y = _toy_data()
        m = _model()
        _run_steps(m, X, y, 4)
        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            eager = m.eval_batch([X[:8]], [y[:8]])
            eager_loss = float(eager["loss"])
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})
        m.eval_batch([X[:8]], [y[:8]])          # sighting
        cap = m.eval_batch([X[:8]], [y[:8]])    # captured
        assert m._captured.stats["captured_steps"] >= 1
        np.testing.assert_allclose(float(cap["loss"]), eager_loss,
                                   rtol=1e-5)

    def test_signature_change_retraces_not_corrupts(self):
        X, y = _toy_data()
        m = _model()
        _run_steps(m, X, y, 4, bs=8)
        c0 = m._captured.stats["compiles"]
        # new batch shape = new signature: sighting then second compile
        for _ in range(3):
            m.train_batch([X[:4]], [y[:4]])
        assert m._captured.stats["compiles"] == c0 + 1
        # freezing a param flips the trainable set = another signature
        m.network[2].bias.stop_gradient = True
        b = m.network[2].bias.numpy().copy()
        for _ in range(3):
            m.train_batch([X[:4]], [y[:4]])
        np.testing.assert_array_equal(m.network[2].bias.numpy(), b)
        m.network[2].bias.stop_gradient = False


class TestSignatureSplit:
    def test_input_label_split_is_part_of_the_signature(self):
        """Same array shapes with a different input/label split must be
        DIFFERENT programs — a collision would run the wrong forward."""
        class TwoWay(nn.Layer):
            def forward(self, a, b=None):
                return a * 2.0 if b is None else a + b

        net = TwoWay()
        step = CapturedStep(net, None, None, strict=False, name="split")
        x = paddle.to_tensor(np.full((4,), 3.0, np.float32))
        y = paddle.to_tensor(np.full((4,), 10.0, np.float32))
        out1, _ = step.forward([x], [y])     # net(x), y is a label
        np.testing.assert_array_equal(out1.numpy(), 6.0)
        out2, _ = step.forward([x, y], [])   # net(x, y): same shapes!
        np.testing.assert_array_equal(out2.numpy(), 13.0)
        out3, _ = step.forward([x], [y])     # first program still right
        np.testing.assert_array_equal(out3.numpy(), 6.0)


class TestTrainStepWrapper:
    def test_trainstep_is_a_captured_step(self):
        from paddle_tpu.jit.api import TrainStep
        paddle.seed(0)
        net = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        step = TrainStep(net, lambda o, t: ((o - t) ** 2).mean(), opt)
        X = np.random.default_rng(0).normal(size=(16, 4)).astype(
            np.float32)
        Y = (X @ np.ones((4, 1), np.float32) * 0.5).astype(np.float32)
        losses = [float(step(X, Y)) for _ in range(10)]
        assert losses[-1] < losses[0] * 0.7, losses
        # TrainStep is explicit whole-step API: captures on call ONE
        # (no first-eager sighting), ignores the kill switch
        assert step._step.stats["compiles"] == 1
        assert step._step.stats["eager_steps"] == 0
        # slot state now lives on the optimizer (state_dict round-trip
        # covers compiled training)
        assert opt._states, "optimizer slot state must be shared"
        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            assert float(step(X, Y)) > 0  # still runs captured
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})

    def test_compile_stats_contract(self):
        from paddle_tpu.jit.api import TrainStep
        net = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        step = TrainStep(net, lambda o, t: ((o - t) ** 2).mean(), opt)
        X = np.zeros((8, 4), np.float32)
        Y = np.zeros((8, 1), np.float32)
        stats = step.compile_stats(X, Y)
        assert stats is not None


class TestGuardMissRetrace:
    def test_guard_miss_discard_retrace_bit_identical(self):
        """The satellite contract: a guard miss discards the speculated
        tail (pure programs, no side effects) and the retraced branch
        serves results BIT-identical to plain eager execution."""
        def f(x):
            y = x * 3.0
            if (y.sum() > 0):
                return (y + 1.0) * 2.0
            return (y - 1.0) * 0.5

        sf = SOTFunction(f)
        pos = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        neg = paddle.to_tensor(np.array([-1.0, -2.0], np.float32))
        before = _sot_snap()
        sf(pos)                                    # record path A
        np.testing.assert_array_equal(sf(pos).numpy(), f(pos).numpy())
        mid = _sot_snap()
        # guard miss: path A speculated on neg, discarded, re-recorded
        np.testing.assert_array_equal(sf(neg).numpy(), f(neg).numpy())
        after = _sot_snap()
        assert after["guard_misses_total"] > \
            mid["guard_misses_total"]
        assert after["retraces_total"] > mid["retraces_total"]
        assert mid["guard_misses_total"] == \
            before.get("guard_misses_total", 0)
        # both branches replay bit-identically afterwards
        np.testing.assert_array_equal(sf(pos).numpy(), f(pos).numpy())
        np.testing.assert_array_equal(sf(neg).numpy(), f(neg).numpy())

    def test_segments_compile_lazily_on_second_replay(self):
        def g(x):
            y = x * 2.0
            bool(y.sum() > 0)  # break: two segments
            return y + 1.0

        sf = SOTFunction(g, name="lazy_seg")
        x = paddle.to_tensor(np.ones(3, np.float32))
        before = _sot_snap()
        sf(x)                                      # record
        sf(x)                                      # replay 1: un-jitted
        mid = _sot_snap()
        assert mid.get("segment_compiles_total", 0) == \
            before.get("segment_compiles_total", 0)
        sf(x)                                      # replay 2: compiles
        after = _sot_snap()
        compiled = after["segment_compiles_total"] - \
            mid.get("segment_compiles_total", 0)
        assert compiled >= 1
        ev = [e for e in flight.events(category="sot")
              if e["name"] == "segment_compile"
              and e["attrs"].get("fn") == "lazy_seg"]
        assert ev, "segment compiles must land in the flight journal"
        sf(x)                                      # replay 3: no growth
        assert _sot_snap()["segment_compiles_total"] == \
            after["segment_compiles_total"]

    def test_guard_budget_flag_forces_eager(self):
        def h(x):
            for _ in range(4):
                float(x.sum())      # 4 guards x 4B
                x = x + 1.0
            return x

        paddle.set_flags({"FLAGS_sot_guard_budget": 8})
        try:
            sf = SOTFunction(h)
            x = paddle.to_tensor(np.ones(3, np.float32))
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                sf(x)
            md = sf.capture_metadata()
            assert any("guard budget" in r
                       for r in md["fallback_reasons"]), md
        finally:
            paddle.set_flags({"FLAGS_sot_guard_budget": 512})


class TestFlightAndMetrics:
    def test_fallback_reason_counted_and_journaled(self):
        def f(x):
            return paddle.nn.functional.dropout(x, 0.5, training=True)

        before = _sot_snap()
        sf = SOTFunction(f, name="rng_fn")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            sf(paddle.to_tensor(np.ones(8, np.float32)))
        after = _sot_snap()
        assert _total(after["fallbacks_total"]) > _total(
            before.get("fallbacks_total", 0))
        cell = om.default_registry().get("sot.fallbacks_total")
        assert cell.value(reason="rng") >= 1
        ev = [e for e in flight.events(category="sot")
              if e["name"] == "fallback"
              and e["attrs"].get("fn") == "rng_fn"]
        assert ev and ev[-1]["attrs"]["reason"] == "rng"

    def test_capture_jit_accounts_and_respects_kill_switch(self):
        from paddle_tpu.jit.sot import capture_jit
        import jax.numpy as jnp
        step = capture_jit(lambda a: a * 2, name="unit.step")
        before = _sot_snap()
        step(jnp.ones((2,)))
        mid = _sot_snap()
        assert mid["captured_compiles_total"] == \
            before["captured_compiles_total"] + 1
        assert mid["captured_steps_total"] == \
            before["captured_steps_total"] + 1
        ev = [e for e in flight.events(category="sot")
              if e["name"] == "capture_compile"
              and e["attrs"].get("fn") == "unit.step"]
        assert ev
        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            out = step(jnp.ones((2,)))  # behavior identical, count muted
            np.testing.assert_array_equal(np.asarray(out), 2.0)
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})
        assert _sot_snap()["captured_steps_total"] == \
            mid["captured_steps_total"]

    def test_serving_decode_is_a_captured_step(self):
        """The serving decode body (clean capture plan checked in)
        routes through capture_jit: steady-state decode counts as
        captured steps."""
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import PagedLlamaDecodeEngine
        paddle.seed(0)
        eng = PagedLlamaDecodeEngine(
            LlamaForCausalLM(LlamaConfig.tiny()), max_slots=2,
            max_seq=32)
        eng.prefill(0, np.array([1, 2, 3], np.int32))
        eng.prefill(1, np.array([4, 5], np.int32))
        before = _sot_snap()
        for _ in range(3):
            eng.step()
        after = _sot_snap()
        assert after["captured_steps_total"] - \
            before["captured_steps_total"] == 3


class TestBucketPolicy:
    def test_bucketed_captured_step_bounds_executables(self):
        """Varlen batches under a pow2 BucketPolicy share a BOUNDED
        captured-executable set (padding semantics are the caller's
        explicit policy, as documented)."""
        paddle.seed(0)
        net = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.0,
                                   parameters=net.parameters())
        step = CapturedStep(
            net, lambda o: (o * 0.0).sum(), opt, strict=False,
            bucket_policy=BucketPolicy({0: {0: "pow2"}}, pad_value=0),
            name="bucketed")
        for n in (3, 4, 5, 7, 6, 8, 5, 3):
            x = paddle.to_tensor(np.ones((n, 4), np.float32))
            assert step.step([x], []) is not None
        # lengths 3..8 -> pow2 buckets {4, 8}: exactly two programs
        assert step.stats["compiles"] == 2, step.stats
