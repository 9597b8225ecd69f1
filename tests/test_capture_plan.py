"""Static capture planner (ISSUE 7): graph-break analysis (PTC001-004),
shape/dtype abstract interpretation + ops.yaml spec golden runs
(PTC005), and the planner that merges static findings with the dynamic
audit into one ranked, consistency-checked capture plan.

Acceptance pins: one seeded break per PTC rule detected by exact id; a
clean jittable step yields an empty plan (zero false positives); a
llama ``Model.fit`` step's plan is consistent with the dynamic audit
(every host sync / op_boundary flush covered or classified
capture-compatible); the serving decode step's checked-in clean-plan
fixture; the CAPTURE_ALLOWLIST stale-entry contract.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import capture, planner, shapes
from paddle_tpu.analysis.capture import scan_source


def _rules(diags):
    return {d.rule for d in diags}


# ---------------------------------------------------------------------------
# static pass: one seeded break per rule, by exact id
# ---------------------------------------------------------------------------

class TestSeededBreaks:
    def test_ptc001_branch_on_tensor(self):
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    if t:\n"
            "        t = paddle.add(t, 1.0)\n"
            "    return t\n")
        assert "PTC001" in _rules(diags)

    def test_ptc001_while_item(self):
        diags = scan_source(
            "def step(x):\n"
            "    while x.item() > 0:\n"
            "        x = paddle.subtract(x, 1.0)\n"
            "    return x\n")
        d = [x for x in diags if x.rule == "PTC001"]
        assert d and "while" in d[0].message

    def test_ptc001_comparison_feeding_branch(self):
        diags = scan_source(
            "def step(x):\n"
            "    loss = paddle.mean(x)\n"
            "    if loss > 0.5:\n"
            "        loss = paddle.add(loss, 1.0)\n"
            "    return loss\n")
        assert "PTC001" in _rules(diags)

    def test_ptc001_builtin_named_tensor_methods_stay_tainted(self):
        # t.sum()/t.abs()/t.max() share builtin names but are tensor
        # ops: the loss/grad-norm check pattern must still flag
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    if t.sum() > 0:\n"
            "        t = paddle.add(t, 1.0)\n"
            "    n = t.abs().max()\n"
            "    if n > 1.0:\n"
            "        t = paddle.divide(t, n)\n"
            "    return t\n")
        assert len([d for d in diags if d.rule == "PTC001"]) == 2
        # ...while the BARE builtins still break taint (host values)
        diags = scan_source(
            "def step(xs):\n"
            "    n = len(xs)\n"
            "    if n > 1:\n"
            "        return paddle.add(xs, 1.0)\n"
            "    return xs\n")
        assert "PTC001" not in _rules(diags)

    def test_ptc001_metadata_branch_not_flagged(self):
        # shape/ndim/dtype are static metadata, not tensor values
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    if t.shape[0] > 1:\n"
            "        t = paddle.add(t, 1.0)\n"
            "    if t is not None:\n"
            "        t = paddle.add(t, 1.0)\n"
            "    return t\n")
        assert "PTC001" not in _rules(diags)

    def test_ptc002_inplace_subscript_store(self):
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    t[0] = 0.0\n"
            "    return t\n")
        assert "PTC002" in _rules(diags)

    def test_ptc002_rng_consumption(self):
        diags = scan_source(
            "def step(x):\n"
            "    noise = paddle.rand([4, 4])\n"
            "    return paddle.add(x, noise)\n")
        d = [x for x in diags if x.rule == "PTC002"]
        assert d and "RNG" in d[0].message

    def test_ptc002_numpy_host_rng_not_flagged(self):
        # host-side data-prep RNG is not device RNG consumption
        diags = scan_source(
            "def step(x):\n"
            "    idx = np.random.uniform(0, 1, (4,))\n"
            "    return paddle.add(x, 1.0)\n")
        assert "PTC002" not in _rules(diags)

    def test_ptc002_self_state_mutation(self):
        diags = scan_source(
            "def step(self, x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    self.history.append(1)\n"
            "    self.count += 1\n"
            "    return t\n", tensor_params=("x",))
        d = [x for x in diags if x.rule == "PTC002"]
        assert len(d) >= 2

    def test_ptc002_host_io(self):
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    print(t)\n"
            "    return t\n")
        d = [x for x in diags if x.rule == "PTC002"]
        assert d and "host I/O" in d[0].message

    def test_ptc003_tail_read_is_hoistable(self):
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    loss = paddle.mean(t)\n"
            "    return loss.item()\n")
        d = [x for x in diags if x.rule == "PTC003"]
        assert d and d[0].data["hoistable"]
        assert "move the fetch after the step" in d[0].hint

    def test_ptc003_midstep_read_needs_guard(self):
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    v = t.numpy()\n"
            "    u = paddle.add(t, 1.0)\n"
            "    return u\n")
        d = [x for x in diags if x.rule == "PTC003"]
        assert d and not d[0].data["hoistable"]

    def test_ptc003_read_in_device_loop_not_hoistable(self):
        # the fetch is the LAST line, but the loop re-enters device work
        diags = scan_source(
            "def step(x):\n"
            "    for i in range(4):\n"
            "        x = paddle.add(x, 1.0)\n"
            "        v = x.item()\n"
            "    return v\n")
        d = [x for x in diags if x.rule == "PTC003"]
        assert d and not d[0].data["hoistable"]

    def test_ptc003_read_before_optimizer_step_not_hoistable(self):
        # the optimizer's update is device work on an untainted
        # receiver: a read before it must NOT be graded hoistable
        diags = scan_source(
            "def step(self, x):\n"
            "    loss = paddle.mean(x)\n"
            "    loss.backward()\n"
            "    v = loss.item()\n"
            "    self.opt.step()\n"
            "    return v\n", tensor_params=("x",))
        d = [x for x in diags if x.rule == "PTC003"]
        assert d and not d[0].data["hoistable"], [x.to_dict()
                                                 for x in d]

    def test_capture_scan_seeds_defaultless_params(self):
        # a live callable's defaultless params are tensor-seeded (the
        # step's data args); params with defaults are config knobs
        def step(x, update=True):
            if x.mean() > 0:
                return x
            if update:
                return x
            return x

        diags, _ = capture.capture_scan(step)
        hits = [d for d in diags if d.rule == "PTC001"]
        assert len(hits) == 1, [d.to_dict() for d in diags]

    def test_loop_carried_taint_chain_reaches_fixpoint(self):
        # a = b; b = c; c = <tensor> around a loop needs one taint
        # pass per hop — the fixpoint loop must find `if a:`
        diags = scan_source(
            "def step(x):\n"
            "    a = 0\n"
            "    b = 0\n"
            "    c = 0\n"
            "    for i in range(3):\n"
            "        if a:\n"
            "            x = paddle.add(x, 1.0)\n"
            "        a = b\n"
            "        b = c\n"
            "        c = paddle.multiply(x, 2.0)\n"
            "    return x\n", tensor_params=("x",))
        assert "PTC001" in _rules(diags)

    def test_ptc003_numpy_host_chain_not_flagged(self):
        diags = scan_source(
            "def step(x):\n"
            "    return np.asarray([1, 2]).item()\n")
        assert "PTC003" not in _rules(diags)

    def test_ptc004_boolean_mask_indexing(self):
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    mask = t > 0.5\n"
            "    return t[mask]\n")
        assert "PTC004" in _rules(diags)

    def test_ptc004_nonzero(self):
        diags = scan_source(
            "def step(x):\n"
            "    return paddle.nonzero(x)\n")
        assert "PTC004" in _rules(diags)

    def test_ptc001_scalar_converter_in_branch(self):
        # `if float(t) > 0:` is data-dependent control flow (PTC001),
        # NOT a hoistable read — a hoist hint here would be wrong
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.mean(x)\n"
            "    if float(t) > 0:\n"
            "        return paddle.add(x, 1.0)\n"
            "    return x\n")
        assert "PTC001" in _rules(diags)
        assert not any(d.rule == "PTC003" and d.data.get("hoistable")
                       for d in diags), [d.to_dict() for d in diags]
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.mean(x)\n"
            "    if bool(t):\n"
            "        return paddle.add(x, 1.0)\n"
            "    return x\n")
        assert "PTC001" in _rules(diags)

    def test_ptc004_integer_gather_not_flagged(self):
        # an integer-tensor gather has the INDEX's static shape; only
        # boolean masks make the result shape data-dependent
        diags = scan_source(
            "def step(x, w, ids):\n"
            "    h = paddle.matmul(x, w)\n"
            "    sel = h[ids]\n"
            "    return paddle.mean(sel)\n")
        assert "PTC004" not in _rules(diags)
        # inline comparison mask still flags
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    return t[t > 0]\n")
        assert "PTC004" in _rules(diags)

    def test_ptc004_static_slicing_not_flagged(self):
        diags = scan_source(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    return t[:, -1]\n")
        assert "PTC004" not in _rules(diags)

    def test_pragma_suppresses_ptc(self, tmp_path):
        p = tmp_path / "step_mod.py"
        p.write_text(
            "def step(x):\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    print(t)  # lint-allow: PTC002 debug tap\n"
            "    return t\n")
        diags, meta = capture.scan_file_function(str(p), "step", ("x",))
        kept, supp = capture.apply_allowlist(diags, meta["pragmas"])
        assert not [d for d in kept if d.rule == "PTC002"]
        assert any(d.rule == "PTC002" for d, _ in supp)


# ---------------------------------------------------------------------------
# zero false positives: a clean jittable step -> empty plan
# ---------------------------------------------------------------------------

class TestCleanStep:
    def test_clean_step_static_scan_is_empty(self):
        diags = scan_source(
            "def step(x, w):\n"
            "    h = paddle.matmul(x, w)\n"
            "    h = paddle.nn.functional.relu(h)\n"
            "    loss = paddle.mean(paddle.multiply(h, h))\n"
            "    return loss\n")
        assert diags == [], [d.to_dict() for d in diags]

    def test_clean_step_plan_is_empty_and_consistent(self):
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        w = paddle.to_tensor(np.ones((8, 8), np.float32) * 0.1)

        def step():
            h = paddle.matmul(x, w)
            h = paddle.nn.functional.relu(h)
            return paddle.mean(paddle.multiply(h, h))

        plan = analysis.capture_plan(step, warmup=2)
        assert plan.diagnostics == [], \
            [d.to_dict() for d in plan.diagnostics]
        assert plan.consistent()
        bad = [b for b in plan.breaks
               if b["classification"] not in ("compatible",)]
        assert bad == [], bad


# ---------------------------------------------------------------------------
# shape/dtype abstract interpreter (PTC005)
# ---------------------------------------------------------------------------

class TestShapesInterpreter:
    def test_abstract_matches_live_representatives(self):
        from paddle_tpu.core import fusion
        cases = [
            ("add", [((3, 4), "float32"), ((4,), "bfloat16")], None),
            ("exp", [((2, 5), "bfloat16")], None),
            ("sum", [((2, 3, 4), "float32")],
             (("axis", (0, 2)), ("dtype", None), ("keepdim", True))),
            ("mean", [((3, 4), "float32")],
             (("axis", None), ("keepdim", False))),
            ("matmul", [((4, 3), "float32"), ((4, 5), "float32")],
             (("transpose_x", True), ("transpose_y", False))),
            ("linear", [((2, 3, 4), "bfloat16"), ((4, 6), "bfloat16"),
                        ((6,), "bfloat16")], ()),
            ("cast", [((3, 4), "float32")],
             (("dtype", np.dtype("bfloat16")),)),
        ]
        for op, avals, attrs in cases:
            got = shapes.abstract_eval(op, avals, attrs)
            want = fusion.infer_output_aval(op, avals, attrs)
            assert got is not None and want is not None, op
            assert got.shape == tuple(want[0]), (op, got, want)
            assert got.dtype == np.dtype(want[1]), (op, got, want)

    def test_all_declared_specs_pass_the_golden_run(self):
        diags = shapes.validate_specs()
        assert diags == [], "\n".join(d.render() for d in diags)

    def test_seeded_wrong_spec_fires_ptc005(self):
        assert _rules(shapes.validate_op("sum", "elementwise")) == \
            {"PTC005"}
        assert _rules(shapes.validate_op("matmul", "broadcast")) == \
            {"PTC005"}

    def test_spec_vocabulary_matches_registry(self):
        from paddle_tpu.ops.op_registry import SHAPE_SPECS
        assert set(shapes._EVALUATORS) == set(SHAPE_SPECS)

    def test_registry_rejects_unknown_or_missing_spec(self):
        from paddle_tpu.ops.op_registry import _norm_shape_spec
        with pytest.raises(ValueError):
            _norm_shape_spec("demo", "reduceish", True)
        with pytest.raises(ValueError):
            _norm_shape_spec("demo", None, "reduce")  # fusable, no spec
        assert _norm_shape_spec("demo", None, False) is None

    def test_interpret_recorded_signature(self):
        """Capture a real fused-program signature via the program
        observer and replay it abstractly: the interpreter's output
        aval must match the actual output, with no PTC005."""
        from paddle_tpu.core import fusion
        sigs = []
        prev = fusion._program_observer
        fusion._program_observer = lambda sig, event: sigs.append(sig)
        try:
            x = paddle.to_tensor(np.ones((4, 8), np.float32))
            y = paddle.to_tensor(np.full((4, 8), 2.0, np.float32))
            out = paddle.mean(
                paddle.multiply(paddle.add(x, y), y), axis=1)
            got = out.numpy()   # flush
        finally:
            fusion._program_observer = prev
        assert sigs, "no fused program was recorded"
        res = shapes.interpret_signature(sigs[-1])
        assert res["diagnostics"] == [], \
            [d.to_dict() for d in res["diagnostics"]]
        assert any(o is not None and o.shape == got.shape
                   and o.dtype == got.dtype for o in res["outputs"]), \
            (res["outputs"], got.shape, got.dtype)

    def test_bucketed_signatures_bound(self):
        sigs = shapes.bucketed_leaf_signatures(
            (8, 128), {1: "pow2"}, 512)
        assert len(sigs) == 10          # pow2 buckets for 1..512
        sigs = shapes.bucketed_leaf_signatures(
            (8, 128), {1: [64, 128, 256, 512]}, 512)
        assert len(sigs) == 4
        # two dynamic axes: the bound is the product, still finite
        sigs = shapes.bucketed_leaf_signatures(
            (8, 128), {0: [8, 16], 1: "pow2"}, 512)
        assert len(sigs) == 20


# ---------------------------------------------------------------------------
# planner: dynamic cross-checks
# ---------------------------------------------------------------------------

class TestPlannerDynamic:
    def test_seeded_sync_becomes_guard_break(self):
        def step():
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            y = paddle.add(paddle.multiply(x, 3.0), 1.0)
            y.numpy()                      # mid-step sync
            z = paddle.multiply(y, 2.0)
            return z

        plan = analysis.capture_plan(step, warmup=1)
        assert plan.consistent(), plan.unaccounted()
        rows = [b for b in plan.breaks
                if b["reason"] in ("host_read", "host_sync")
                and b["classification"] in ("guard", "hoist")]
        assert rows, plan.breaks
        assert any(b["rule"] == "PTC003" for b in rows)
        # the mid-step read must NOT be classified hoistable
        assert any(b["classification"] == "guard" for b in rows)

    def test_shape_churn_synthesizes_ptc004_bucket_row(self):
        from paddle_tpu.core import fusion
        fusion.clear_cache()  # earlier tests may have compiled these
        # exact chain structures — churn only shows on a cold cache

        def churn():
            for n in range(3, 9):
                x = paddle.to_tensor(np.ones((n,), np.float32))
                y = paddle.add(paddle.multiply(x, 2.0), 1.0)
                y.numpy()

        try:
            plan = analysis.capture_plan(churn, warmup=1)
        finally:
            # don't leave these structures warm for OTHER churn tests
            # (test_analysis.py uses the same chain/shapes)
            fusion.clear_cache()
        rows = [b for b in plan.breaks
                if b["classification"] == "bucket"]
        assert rows, plan.breaks
        assert any(d.rule == "PTC004" for d in plan.diagnostics)
        assert any("BucketPolicy" in (b["fix"] or "") for b in rows)

    def test_bound_method_step_not_double_scanned(self):
        """The fn scan and the enclosing-origin scan name functions
        differently (__qualname__ vs bare name); dedupe is by source
        span, so a bound-method step is scanned ONCE."""
        from paddle_tpu.hapi import Model
        import paddle_tpu.nn as nn
        net = nn.Linear(4, 4)
        m = Model(net)
        m.prepare(loss=nn.MSELoss())
        x = np.ones((2, 4), np.float32)

        def step():
            m.eval_batch([x], [x])

        plan = analysis.capture_plan(step, warmup=1)
        spans = [(f["file"], tuple(f["span"])) for f in plan.functions]
        assert len(spans) == len(set(spans)), spans
        locs = [d.location for d in plan.static_diags] + \
            [d.location for d, _ in plan.suppressed]
        assert len(locs) == len(set(locs)), locs

    def test_plan_renders_and_dicts(self):
        def step():
            x = paddle.to_tensor(np.ones((4,), np.float32))
            return paddle.add(x, 1.0)

        plan = analysis.capture_plan(step, warmup=1)
        text = plan.render()
        assert "capture plan" in text and "consistent" in text
        d = plan.to_dict()
        assert "breaks" in d and "consistent" in d


# ---------------------------------------------------------------------------
# the acceptance test: llama Model.fit step, static ∪ dynamic consistent
# ---------------------------------------------------------------------------

class TestLlamaPlanConsistency:
    def _fit_model(self):
        from paddle_tpu.hapi import Model
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        paddle.seed(0)
        net = LlamaForCausalLM(LlamaConfig.tiny())
        m = Model(net)
        m.prepare(optimizer=paddle.optimizer.AdamW(
            learning_rate=1e-3, parameters=net.parameters()),
            loss=LlamaPretrainingCriterion())
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 128, (2, 16)).astype(np.int64)
        return m, ids

    def test_fit_step_plan_consistent_with_audit(self):
        """The EAGER plan (FLAGS_sot_capture=0): the per-chain path the
        planner audited before Fusion III implemented it. The loss
        fetch is now HOISTED out of train_batch, so the plan has no
        hapi sync row at all — and no allowlist entry carrying it."""
        m, ids = self._fit_model()

        def step():
            m.train_batch([ids], [ids])

        paddle.set_flags({"FLAGS_sot_capture": 0})
        try:
            plan = analysis.capture_plan(step, warmup=3)
        finally:
            paddle.set_flags({"FLAGS_sot_capture": 1})
        # the consistency contract: every PTA001 host sync and every
        # op_boundary flush site is covered by a PTC diagnostic with a
        # fix hint or classified capture-compatible
        assert plan.consistent(), plan.unaccounted()
        assert plan.breaks, "an eager llama train step has break rows"
        for b in plan.breaks:
            assert b["classification"] != "unaccounted", b
            assert b["fix"], b
        # the historical hapi loss fetch is GONE (hoisted to the fit
        # log boundary): no sync row, no PTC003, no allowlist carry
        hapi_rows = [b for b in plan.breaks
                     if "hapi/model.py" in b["site"]
                     and b["reason"] in ("host_sync", "host_read")]
        assert hapi_rows == [], hapi_rows
        assert not any("hapi/model.py" in d.location
                       and d.rule == "PTC003"
                       for d, _ in plan.suppressed)
        # op_boundary rows rank by measured flush cost and are absorbed
        ob = [b for b in plan.breaks if b["reason"] == "op_boundary"]
        assert ob and all(b["classification"] == "compatible"
                          for b in ob)
        assert ob == sorted(ob, key=lambda b: -b["count"])
        # no steady-state churn, so no bucket rows on the clean step
        assert not [b for b in plan.breaks
                    if b["classification"] == "bucket"]

    def test_flash_attention_step_plans_capturable(self):
        """ISSUE 16 satellite (ROADMAP item-3 step-one residue): a
        transformer step routed through the REAL flash-attention entry
        point (LlamaConfig.tiny() defaults use_flash_attention=True,
        so llama_attention dispatches ops.pallas.flash_attention)
        produces a consistent capture plan — and the planner's
        abstract interpreter resolves the attention aval through the
        declared `shape: attention` spec instead of treating the op
        as an opaque boundary."""
        from paddle_tpu.analysis import shapes
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny()
        assert cfg.use_flash_attention
        paddle.seed(0)
        net = LlamaForCausalLM(cfg)
        ids = paddle.to_tensor(
            (np.arange(32, dtype=np.int64) % 64).reshape(2, 16))

        def step():
            out = net(ids)
            logits = out[0] if isinstance(out, (tuple, list)) else out
            return paddle.mean(logits)

        plan = analysis.capture_plan(step, warmup=2)
        assert plan.consistent(), plan.unaccounted()
        assert not [b for b in plan.breaks
                    if b["classification"] == "unaccounted"]
        # non-vacuous spec resolution: q/k/v avals in, query aval out
        got = shapes.abstract_eval(
            "flash_attention", [((2, 16, 4, 8), "float32")] * 3, ())
        assert got is not None and got.shape == (2, 16, 4, 8)
        assert str(got.dtype) == "float32"

    def test_captured_fit_step_runs_dispatch_free(self):
        """ISSUE 10 acceptance, audit as the assertion engine: a
        steady-state captured llama train step is ONE executable call
        with ZERO host syncs and ZERO flushes inside the captured
        region, the plan stays CONSISTENT, and the kill switch restores
        eager per-chain fusion (the PR 6 -> 7 -> 10 loop closed)."""
        from paddle_tpu.observability import metrics as om
        m, ids = self._fit_model()

        def step():
            m.train_batch([ids], [ids])

        plan = analysis.capture_plan(step, warmup=3)
        assert plan.consistent(), plan.unaccounted()
        rep = plan.capture
        assert rep.syncs == [], rep.syncs
        assert len(rep.flushes) <= 3, rep.flushes   # a handful, not N
        assert rep.pair_builds == [] and rep.step_builds == []
        assert not [d for d in rep.diagnostics
                    if d.rule in ("PTA001", "PTA002", "PTA003")], \
            [d.to_dict() for d in rep.diagnostics]
        # <= 3 jitted executable calls per step (here: exactly one)
        before = dict(om.snapshot().get("sot", {}))
        m.train_batch([ids], [ids])
        after = dict(om.snapshot().get("sot", {}))
        captured = after.get("captured_steps_total", 0) - \
            before.get("captured_steps_total", 0)
        assert 1 <= captured <= 3, captured
        assert after.get("guard_misses_total", 0) == \
            before.get("guard_misses_total", 0)


# ---------------------------------------------------------------------------
# repo step functions: serving decode clean-plan fixture + allowlist
# ---------------------------------------------------------------------------

class TestRepoStepFixtures:
    def test_serving_decode_impl_is_clean(self):
        """The jitted decode/prefill bodies are the capture regions:
        zero findings, even unallowlisted (block-table walk, streaming
        attention, pool scatter all stay functional)."""
        import os
        from paddle_tpu.analysis.lint import REPO_ROOT
        path = os.path.join(REPO_ROOT, "paddle_tpu", "serving.py")
        for qual, params in [
            ("PagedLlamaDecodeEngine._decode_impl",
             ("params", "kv", "last_ids", "pos", "tables", "act")),
            ("PagedLlamaDecodeEngine._prefill_impl",
             ("params", "kv", "ids", "table_row", "start", "nvalid",
              "true_len")),
            ("PagedLlamaDecodeEngine._propose_impl",
             ("params", "kv", "last_ids", "pos", "tables", "act")),
            ("PagedLlamaDecodeEngine._spec_verify_impl",
             ("params", "kv", "last_ids", "draft_tok", "pos",
              "tables", "act")),
        ]:
            diags, _ = capture.scan_file_function(path, qual, params)
            assert diags == [], (qual, [d.to_dict() for d in diags])

    def test_serving_decode_step_clean_plan_fixture(self):
        """Checked-in expectation for the decode step/window/prefill
        loops: the ONLY raw findings are the known
        slot/block bookkeeping mutations (PTC002) and the designed
        per-step/window/first-token fetch (PTC003, hoisted to the
        tail) — all allowlisted, so the effective plan is clean.
        Feeds ROADMAP item 2."""
        import os
        from paddle_tpu.analysis.lint import REPO_ROOT
        path = os.path.join(REPO_ROOT, "paddle_tpu", "serving.py")
        expected = {
            # step_enqueue: pos advances where the launch goes out;
            # step_collect: the fetched tokens land in last_ids (the
            # fetch reads a launch record's entry, which the scan does
            # not follow)
            "PagedLlamaDecodeEngine.step_enqueue": {"PTC002": 1},
            "PagedLlamaDecodeEngine.step_collect": {"PTC002": 1},
            "PagedLlamaDecodeEngine.decode_steps":
                {"PTC002": 1, "PTC003": 1},
            # begin_request: admission bookkeeping only — slot
            # activation (pos/active), prefill staging, and the
            # prefix-sharing hit record; the radix match/alias/COW
            # decision is allocator method calls, not step-state
            # mutation, so it adds NO findings beyond the hit record
            "PagedLlamaDecodeEngine.begin_request": {"PTC002": 4},
            # prefill_enqueue: prompt staging into the padded host
            # buffer (the per-bucket program-cache insert lives in
            # _prefill_program, no step) and slot activation
            # bookkeeping (pos/active, the activation's count, the
            # first token's handle for the next launch);
            # prefill_collect: the fetched first
            # token lands in last_ids and seeds the draft's mirror (the
            # radix commit_prefix after each chunk is an allocator
            # call — no new finding)
            "PagedLlamaDecodeEngine.prefill_enqueue": {"PTC002": 5},
            "PagedLlamaDecodeEngine.prefill_collect": {"PTC002": 2},
            # spec_step: commit bookkeeping (pos/last_ids) between the
            # propose/verify executables + the ONE window fetch
            # (tokens + accepted counts, both hoisted to the tail)
            "PagedLlamaDecodeEngine.spec_step":
                {"PTC002": 2, "PTC003": 2},
        }
        for qual, want in expected.items():
            diags, meta = capture.scan_file_function(path, qual, ())
            got = {}
            for d in diags:
                got[d.rule] = got.get(d.rule, 0) + 1
            assert got == want, (qual, [d.to_dict() for d in diags])
            # every token fetch is already at the tail (hoisted form)
            for d in diags:
                if d.rule == "PTC003":
                    assert d.data["hoistable"], d.to_dict()
            kept, supp = capture.apply_allowlist(
                diags, meta.get("pragmas"))
            assert kept == [], [d.to_dict() for d in kept]

    # (the clean-after-allowlist gate itself lives in
    # tests/test_lint_clean.py::test_repo_step_functions_capture_clean
    # — the tier-1 CI contract; not duplicated here)

    def test_static_repo_plan_consistent(self):
        plan = planner.plan_repo_steps()
        assert plan.consistent()
        assert plan.regions and len(plan.regions) >= 5

    def test_capture_allowlist_entries_all_match(self):
        """Stale-entry contract (the lint allowlist's rule, for PTC):
        every CAPTURE_ALLOWLIST entry must still suppress at least one
        raw finding."""
        import fnmatch
        from paddle_tpu.analysis.allowlist import CAPTURE_ALLOWLIST
        raw = capture.scan_repo_steps(use_allowlist=False)
        for rule, pattern, why in CAPTURE_ALLOWLIST:
            assert len(why.split()) >= 4, (rule, pattern, why)
            hit = any(
                d.rule == rule and (
                    fnmatch.fnmatch(d.location.partition(":")[0],
                                    pattern)
                    or fnmatch.fnmatch(d.location, pattern)
                    or fnmatch.fnmatch(d.message, pattern))
                for d in raw.diagnostics)
            assert hit, (f"CAPTURE_ALLOWLIST entry ({rule}, "
                         f"{pattern!r}) matches no finding — fixed "
                         f"site? delete the entry")

    def test_hapi_loss_fetch_hoisted(self):
        """Fusion III hoisted the hapi loss fetch: train_batch/
        eval_batch scan with ZERO raw findings (no .item() left to
        allowlist — the stale-entry contract forced the entry out),
        and the fetch now lives at the fit/evaluate log boundary."""
        raw = capture.scan_repo_steps(use_allowlist=False)
        hapi = [d for d in raw.diagnostics
                if "hapi/model.py" in d.location]
        assert hapi == [], [d.to_dict() for d in hapi]
        from paddle_tpu.analysis.allowlist import CAPTURE_ALLOWLIST
        assert not any("hapi" in pattern
                       for _, pattern, _ in CAPTURE_ALLOWLIST)


# ---------------------------------------------------------------------------
# CLI + self-check integration
# ---------------------------------------------------------------------------

class TestSurface:
    def test_cli_capture_plan(self, capsys):
        from paddle_tpu.analysis.__main__ import main
        assert main(["--capture-plan"]) == 0
        out = capsys.readouterr().out
        assert "capture plan" in out
        assert main(["--capture-plan", "--json"]) == 0
        import json
        d = json.loads(capsys.readouterr().out)
        assert d["consistent"] is True

    def test_self_check_exercises_ptc_rules(self):
        from paddle_tpu.analysis.report import self_check
        out = self_check()
        assert out["ok"], out
        assert out["checks"].get("capture") is True
        assert out["checks"].get("shapes") is True

    def test_rules_table_has_ptc_family(self):
        from paddle_tpu.analysis.diagnostics import RULES
        for rid in ("PTC001", "PTC002", "PTC003", "PTC004", "PTC005"):
            assert rid in RULES
            assert RULES[rid].analyzer == "capture"

    def test_lazy_exports(self):
        assert callable(analysis.capture_plan)
        assert callable(analysis.capture_scan)
        assert analysis.CapturePlan is planner.CapturePlan
