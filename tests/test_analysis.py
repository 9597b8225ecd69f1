"""paddle_tpu.analysis: program auditor, source linter, lock checker.

Seeded-bug fixtures (ISSUE 6 acceptance): a synthetic use-after-donate,
an injected host sync in a fused chain, a cache-key churn loop and a
deliberate lock-order cycle — each detected by its exact rule id — plus
a zero-false-positive capture audit over a clean llama train step whose
report enumerates every flush boundary with reason AND origin.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import audit, lint, report
from paddle_tpu.analysis.auditor import Auditor
from paddle_tpu.analysis.diagnostics import RULES, Diagnostic
from paddle_tpu.analysis.lint import lint_source
from paddle_tpu.analysis import locks as alocks
from paddle_tpu.analysis.report import self_check
from paddle_tpu.core.flags import set_flags


def _rules(diags):
    return {d.rule for d in diags}


# ---------------------------------------------------------------------------
# lint engine (AST rules on seeded source)
# ---------------------------------------------------------------------------

class TestLintEngine:
    def test_bare_except_detected(self):
        diags = lint_source(
            "def f():\n"
            "    try:\n"
            "        run()\n"
            "    except:\n"
            "        pass\n")
        assert "PTL004" in _rules(diags)

    def test_host_sync_detected(self):
        diags = lint_source(
            "def f(t):\n"
            "    return t.numpy()\n")
        assert "PTL001" in _rules(diags)

    def test_item_on_chained_call_not_flagged(self):
        # np.asarray(...).item() is a host->host numpy idiom, not a
        # device sync — the receiver heuristic must skip it
        diags = lint_source(
            "import numpy as np\n"
            "def f(x):\n"
            "    return np.asarray(x).item()\n")
        assert "PTL001" not in _rules(diags)
        diags = lint_source(
            "import numpy as np\n"
            "def f(x):\n"
            "    return np.cumsum(x).tolist()\n")
        assert "PTL001" not in _rules(diags)

    def test_item_on_chained_device_call_flagged(self):
        # loss.mean().item() IS a device sync — the numpy-idiom
        # exemption must not swallow chained device calls
        diags = lint_source(
            "def f(loss):\n"
            "    return loss.mean().item()\n")
        assert "PTL001" in _rules(diags)

    def test_unguarded_registry_mutation_detected(self):
        diags = lint_source(
            "CACHE = {}\n"
            "def evict():\n"
            "    CACHE.clear()\n")
        assert "PTL003" in _rules(diags)

    def test_guarded_mutation_not_flagged(self):
        diags = lint_source(
            "import threading\n"
            "CACHE = {}\n"
            "_lock = threading.Lock()\n"
            "def evict():\n"
            "    with _lock:\n"
            "        CACHE.clear()\n")
        assert "PTL003" not in _rules(diags)

    def test_memo_insert_not_flagged(self):
        # single-assignment memo inserts are GIL-atomic by design
        diags = lint_source(
            "CACHE = {}\n"
            "def put(k, v):\n"
            "    CACHE[k] = v\n")
        assert "PTL003" not in _rules(diags)

    def test_del_while_sweeping_detected(self):
        # the exact pattern the alias registry had before PR 6
        diags = lint_source(
            "REG = {}\n"
            "def sweep():\n"
            "    for k in [k for k, d in REG.items() if not d]:\n"
            "        del REG[k]\n")
        assert "PTL003" in _rules(diags)

    def test_inline_pragma_suppresses(self, tmp_path):
        p = tmp_path / "snippet.py"
        p.write_text("CACHE = {}\n"
                     "def evict():\n"
                     "    CACHE.clear()  # lint-allow: PTL003 teardown\n")
        r = lint(paths=[str(p)])
        assert not [d for d in r.diagnostics if d.rule == "PTL003"]
        assert any(d.rule == "PTL003" for d, _ in r.suppressed)

    def test_unknown_rule_defaults_severity(self):
        d = Diagnostic("PTL004", "x.py:1", "m")
        assert d.severity == RULES["PTL004"].severity == "error"


class TestLintRepo:
    def test_flag_read_facts_cover_wired_flags(self):
        """The flags PR 6 wired (benchmark, retain_grad_for_all_tensor)
        must no longer appear as PTL002 findings."""
        r = lint()
        locs = [d.message for d in r.diagnostics if d.rule == "PTL002"]
        assert not any("benchmark" in m for m in locs)
        assert not any("retain_grad_for_all_tensor" in m for m in locs)

    def test_allowlist_entries_all_match_something(self):
        """A stale allowlist entry (site fixed but entry kept) is dead
        weight — every entry must still suppress at least one raw
        finding."""
        from paddle_tpu.analysis.allowlist import ALLOWLIST
        raw = lint(use_allowlist=False)
        import fnmatch
        for rule, pattern, _why in ALLOWLIST:
            hit = any(
                d.rule == rule and (
                    fnmatch.fnmatch(d.location.partition(":")[0], pattern)
                    or fnmatch.fnmatch(d.location, pattern)
                    or fnmatch.fnmatch(d.message, pattern))
                for d in raw.diagnostics)
            assert hit, (f"allowlist entry ({rule}, {pattern!r}) matches "
                         f"no finding — fixed site? delete the entry")


# ---------------------------------------------------------------------------
# program auditor: seeded bugs
# ---------------------------------------------------------------------------

class TestAuditorSeededBugs:
    def test_host_sync_in_fused_chain(self):
        """An injected .numpy() mid-chain must surface as PTA001 AND as
        a host_read flush whose origin points at THIS file."""
        def step():
            x = paddle.to_tensor(np.ones((8, 8), np.float32))
            y = paddle.add(paddle.multiply(x, 3.0), 1.0)
            y.numpy()                      # seeded host sync
            z = paddle.multiply(y, 2.0)
            return z.numpy()

        rep = audit(step, warmup=1)
        assert any(d.rule == "PTA001" for d in rep.diagnostics)
        host_reads = [f for f in rep.flushes if f["reason"] == "host_read"]
        assert host_reads, rep.flushes
        assert any("test_analysis.py" in f["origin"] for f in host_reads)
        assert any("test_analysis.py" in s["origin"] for s in rep.syncs)

    def test_use_after_donate(self):
        """A live handle wrapping a deleted (donated) buffer must be
        found by the post-run sweep as PTA002."""
        holder = []

        def step():
            x = paddle.to_tensor(np.ones((8,), np.float32))
            holder.append(x)
            # simulate what XLA donation does to the input buffer: the
            # handle keeps pointing at a deleted array
            x._data.delete()

        rep = audit(step, warmup=0)
        holder.clear()
        assert any(d.rule == "PTA002" for d in rep.diagnostics), \
            [d.to_dict() for d in rep.diagnostics]
        assert rep.use_after_donate

    def test_read_of_donated_buffer_attributed(self):
        """Reading a deleted buffer through .numpy() is caught AT the
        read with call-site attribution (before the crash)."""
        def step():
            x = paddle.to_tensor(np.ones((4,), np.float32))
            x._data.delete()
            try:
                x.numpy()
            except Exception:
                pass  # the read itself fails; the audit still records it

        rep = audit(step, warmup=0)
        uad = [d for d in rep.diagnostics if d.rule == "PTA002"]
        assert uad
        assert any("test_analysis.py" in d.location for d in uad)

    def test_crashing_step_still_ships_the_report(self):
        """A real use-after-donate CRASHES the measured run; the audit's
        whole point is the attribution recorded up to the crash — it
        rides the exception as .capture_report."""
        def step():
            x = paddle.to_tensor(np.ones((4,), np.float32))
            x.numpy()                      # recorded sync
            x._data.delete()
            x.numpy()                      # raises on the deleted buffer

        with pytest.raises(Exception) as ei:
            audit(step, warmup=0)
        rep = getattr(ei.value, "capture_report", None)
        assert rep is not None
        assert any(d.rule == "PTA001" for d in rep.diagnostics)
        assert any(d.rule == "PTA002" for d in rep.diagnostics)

    def test_recompile_churn_loop(self):
        """A shape-polymorphic call site keeps compiling in the measured
        window -> PTA003 naming the shape churn."""
        from paddle_tpu.core import fusion
        fusion.clear_cache()  # churn needs a cold program cache: other
        # tests (e.g. test_capture_plan) use the same chain structures

        def churn():
            for n in range(3, 9):
                x = paddle.to_tensor(np.ones((n,), np.float32))
                y = paddle.add(paddle.multiply(x, 2.0), 1.0)
                y.numpy()

        rep = audit(churn, warmup=1)
        churn_d = [d for d in rep.diagnostics if d.rule == "PTA003"]
        assert churn_d, [d.to_dict() for d in rep.diagnostics]
        assert any("shape-polymorphic" in d.message for d in churn_d)

    def test_steady_state_chain_is_churn_free(self):
        """Same shapes every iteration: after warmup the measured run
        must be compile-free (no PTA003 false positive)."""
        def step():
            x = paddle.to_tensor(np.ones((8,), np.float32))
            y = paddle.add(paddle.multiply(x, 2.0), 1.0)
            y.numpy()

        rep = audit(step, warmup=2)
        assert not [d for d in rep.diagnostics if d.rule == "PTA003"], \
            [d.to_dict() for d in rep.diagnostics]
        assert not rep.fusion_compiles


# ---------------------------------------------------------------------------
# program auditor: clean llama train step (zero false positives)
# ---------------------------------------------------------------------------

class TestAuditorLlamaStep:
    def _fit_step(self):
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

        def step():
            m.train_batch([ids], [ids])

        return step

    def test_capture_report_enumerates_flushes_no_false_positives(self):
        """The EAGER planning input (Fusion III implemented the plan;
        FLAGS_sot_capture=0 pins that the per-chain path the planner
        audited still behaves and attributes as before)."""
        step = self._fit_step()
        set_flags({"FLAGS_sot_capture": 0})
        try:
            rep = audit(step, warmup=3)
        finally:
            set_flags({"FLAGS_sot_capture": 1})
        # the capture report enumerates flush boundaries with reason
        # AND origin — the Fusion III planning input
        assert rep.flushes, "an eager llama train step must flush"
        assert all(f["reason"] for f in rep.flushes)
        assert all(f["origin"] != "<unknown>" for f in rep.flushes)
        assert rep.flush_sites(), "aggregated top-N flush sites"
        # zero false positives on clean code: no use-after-donate, no
        # steady-state recompile churn
        assert not [d for d in rep.diagnostics if d.rule == "PTA002"], \
            [d.to_dict() for d in rep.diagnostics]
        assert not [d for d in rep.diagnostics if d.rule == "PTA003"], \
            [d.to_dict() for d in rep.diagnostics]
        # the loss fetch is HOISTED out of train_batch (Fusion III):
        # even the eager step is sync-free in its measured window
        assert not [d for d in rep.diagnostics if d.rule == "PTA001"], \
            [d.to_dict() for d in rep.diagnostics]


# ---------------------------------------------------------------------------
# lock-order checker
# ---------------------------------------------------------------------------

class TestWiredFlags:
    """Behavioral contracts for the two flags PR 6 wired (a lint-absence
    check alone can't prove the documented behavior exists — PTL002's
    own lesson)."""

    def test_benchmark_flag_forces_eager_dispatch(self):
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        y = paddle.add(paddle.multiply(x, 2.0), 1.0)
        assert y._lazy is not None  # normally: deferred into the DAG
        y.numpy()
        set_flags({"FLAGS_benchmark": 1})
        try:
            z = paddle.add(paddle.multiply(x, 2.0), 1.0)
            # sync-after-each-op requires each op to actually dispatch
            assert z._lazy is None
        finally:
            set_flags({"FLAGS_benchmark": 0})

    def test_retain_all_flag_populates_interior_and_root_grads(self):
        def run():
            x = paddle.to_tensor(np.ones(3, np.float32),
                                 stop_gradient=False)
            h = paddle.multiply(x, 2.0)
            loss = h.sum()
            loss.backward()
            return x, h, loss

        x0, h0, l0 = run()
        assert x0.grad is not None and h0.grad is None and l0.grad is None
        set_flags({"FLAGS_retain_grad_for_all_tensor": 1})
        try:
            x1, h1, l1 = run()
        finally:
            set_flags({"FLAGS_retain_grad_for_all_tensor": 0})
        assert x1.grad is not None
        np.testing.assert_allclose(h1.grad.numpy(), np.ones(3))
        np.testing.assert_allclose(l1.grad.numpy(), 1.0)


class TestLockChecker:
    def test_seeded_cycle_detected(self):
        aud = alocks.LockAuditor()
        a, b = aud.lock("A"), aud.lock("B")

        def ab():
            with a, b:
                pass

        def ba():
            with b, a:
                pass

        ab()
        t = threading.Thread(target=ba)
        t.start()
        t.join()
        diags = aud.diagnostics()
        assert any(d.rule == "PTK001" for d in diags)
        assert aud.cycles()
        # summary() composes cycles + bookkeeping without deadlocking
        assert aud.summary()["cycles"] == ["A -> B -> A"]

    def test_cross_thread_release_no_phantom_hold(self):
        """threading.Lock handoff: acquired on one thread, released on
        another — the acquirer's hold must be evicted, not poison every
        later nesting edge on that thread."""
        aud = alocks.LockAuditor()
        lk, other = aud.lock("L"), aud.lock("X")
        lk.acquire()
        t = threading.Thread(target=lk.release)
        t.start()
        t.join()
        assert not aud.held_now()
        with other:
            pass
        assert ("L", "X") not in aud.edges

    def test_condition_on_patched_rlock_reentrant_wait(self):
        """threading.Condition probes _release_save/_acquire_restore on
        its lock; the shim must delegate them or a reentrant holder's
        wait() releases one level and deadlocks."""
        done = []
        with alocks.instrument():
            cond = threading.Condition()   # patched RLock underneath

            def waiter():
                with cond:
                    with cond:             # reentrant hold
                        cond.wait(timeout=10)
                        done.append(True)

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.1)
            with cond:
                cond.notify_all()
            t.join(timeout=10)
            assert not t.is_alive(), "reentrant Condition.wait deadlocked"
        assert done

    def test_closed_auditor_degrades_to_plain_lock(self):
        """Objects built under instrument() keep their locks for life;
        after the context exits they must stop recording (and paying
        the stack walk) entirely."""
        with alocks.instrument(patch_threading=False) as aud:
            lk = alocks.make_lock("survivor")
            with lk:
                pass
        n = aud.acquisitions.get("survivor")
        with lk:
            pass
        assert aud.acquisitions.get("survivor") == n

    def test_consistent_order_is_clean(self):
        aud = alocks.LockAuditor()
        a, b = aud.lock("A"), aud.lock("B")
        for _ in range(3):
            with a, b:
                pass
        assert not aud.cycles()
        assert not [d for d in aud.diagnostics() if d.rule == "PTK001"]

    def test_device_op_under_lock_detected(self):
        with alocks.instrument(patch_threading=False) as aud:
            lk = aud.lock("test.device_hold")
            with lk:
                x = paddle.to_tensor(np.ones((4,), np.float32))
                y = paddle.add(paddle.multiply(x, 2.0), 1.0)
                y.numpy()   # fusion flush while holding the lock
        diags = aud.diagnostics()
        assert any(d.rule == "PTK002" and "fusion_flush" in d.message
                   for d in diags), [d.to_dict() for d in diags]

    def test_make_lock_routes_to_active_auditor(self):
        from paddle_tpu.analysis.locks import make_lock
        plain = make_lock("x")
        assert not isinstance(plain, alocks.InstrumentedLock)
        with alocks.instrument(patch_threading=False):
            inst = make_lock("x")
            assert isinstance(inst, alocks.InstrumentedLock)


class _MemStore:
    """Minimal in-memory store surface for ElasticManager."""

    def __init__(self):
        self._d = {}
        self._lock = threading.Lock()

    def get_nowait(self, k):
        return self._d.get(k)

    def set(self, k, v):
        with self._lock:
            self._d[k] = v

    def add(self, k, n):
        with self._lock:
            v = int(self._d.get(k, 0)) + n
            self._d[k] = v
            return v

    def delete(self, k):
        with self._lock:
            self._d.pop(k, None)


class TestSubsystemLockOrder:
    """PR 2's threads had never been order-checked. This is the
    regression test proving the ordering is clean (the satellite's
    'if none reproduce' branch): async checkpoint, serving drain and
    elastic watch run under full lock instrumentation and must produce
    no lock-order cycle."""

    def test_checkpoint_serving_elastic_no_cycles(self, tmp_path):
        from paddle_tpu.framework.checkpoint import CheckpointManager
        from paddle_tpu.serving import GenerationServer
        from paddle_tpu.distributed.elastic import ElasticManager
        from tests.test_flight import FakeEngine

        with alocks.instrument(long_hold_s=30.0) as aud:
            # async checkpoint: concurrent writer + reader
            mgr = CheckpointManager(str(tmp_path), keep_n=2,
                                    async_save=True)
            stop = threading.Event()

            def reader():
                while not stop.is_set():
                    mgr.latest()
                    time.sleep(0.001)

            rt = threading.Thread(target=reader)
            rt.start()
            for step in range(4):
                mgr.save({"w": np.arange(8, dtype=np.float32)}, step)
            mgr.wait()
            stop.set()
            rt.join()
            assert mgr.restore() is not None
            mgr.close()

            # serving: submit/drain under load
            srv = GenerationServer(FakeEngine(slots=2))
            reqs = [srv.submit([1, 2, 3], max_new_tokens=4)
                    for _ in range(5)]
            assert srv.shutdown(drain=True, timeout=30)
            for r in reqs:
                assert r["done"].is_set()

            # elastic: heartbeat + watch threads over a fake store
            em = ElasticManager(_MemStore(), "0", ttl=0.5, interval=0.05,
                                stability_ticks=1)
            em.start()
            time.sleep(0.3)
            em._watch_tick()   # user-driven tick racing the thread
            em.stop()

        cycles = aud.cycles()
        assert not cycles, f"lock-order cycles: {cycles}"
        assert not [d for d in aud.diagnostics() if d.rule == "PTK001"]
        # the named subsystem locks actually went through the shim
        names = set(aud.acquisitions)
        assert any(n.startswith("checkpoint.manager") for n in names)
        assert any(n.startswith("serving.submit") for n in names)
        assert any(n.startswith("elastic.watch_tick") for n in names)


# ---------------------------------------------------------------------------
# flush-site metrics (satellite: stack-origin attribution)
# ---------------------------------------------------------------------------

class TestFlushSiteMetrics:
    def test_flag_populates_site_labeled_counter(self):
        from paddle_tpu.core import fusion
        fusion._M_flush_sites.reset()
        set_flags({"FLAGS_fusion_flush_origin": 1})
        try:
            x = paddle.to_tensor(np.ones((4,), np.float32))
            y = paddle.add(paddle.multiply(x, 2.0), 1.0)
            y.numpy()
        finally:
            set_flags({"FLAGS_fusion_flush_origin": 0})
        series = fusion._M_flush_sites.series()
        labeled = [dict(k) for k in series if k]
        assert any("test_analysis.py" in c.get("site", "")
                   and c.get("reason") == "host_read" for c in labeled), \
            series

    def test_flag_off_is_free(self):
        from paddle_tpu.core import fusion
        fusion._M_flush_sites.reset()
        x = paddle.to_tensor(np.ones((4,), np.float32))
        y = paddle.add(paddle.multiply(x, 2.0), 1.0)
        y.numpy()
        assert not [k for k in fusion._M_flush_sites.series() if k]

    def test_site_cardinality_cap_collapses_to_other(self):
        """ISSUE 7 satellite: a long-lived process must not grow one
        counter cell per distinct call site forever — past the cap new
        sites land in '<other>', so planner attribution can't blow up
        metric cardinality. Known sites keep their own label."""
        from paddle_tpu.core import fusion
        fusion._M_flush_sites.reset()
        saved = set(fusion._seen_flush_sites)
        try:
            fusion._seen_flush_sites.clear()
            fusion._seen_flush_sites.update(
                f"fake/site_{i}.py:1" for i in range(
                    fusion._MAX_FLUSH_SITES))
            set_flags({"FLAGS_fusion_flush_origin": 1})
            try:
                x = paddle.to_tensor(np.ones((4,), np.float32))
                y = paddle.add(paddle.multiply(x, 2.0), 1.0)
                y.numpy()
            finally:
                set_flags({"FLAGS_fusion_flush_origin": 0})
            labels = {dict(k).get("site")
                      for k in fusion._M_flush_sites.series() if k}
            assert "<other>" in labels, labels
            assert not any(l and "test_analysis.py" in l
                           for l in labels), labels
            # the set itself must not have grown past the cap
            assert len(fusion._seen_flush_sites) <= \
                fusion._MAX_FLUSH_SITES
        finally:
            fusion._seen_flush_sites.clear()
            fusion._seen_flush_sites.update(saved)
            fusion._M_flush_sites.reset()


# ---------------------------------------------------------------------------
# report surface + self-check
# ---------------------------------------------------------------------------

class TestReportSurface:
    def test_report_composes_capture_and_lint(self):
        def step():
            x = paddle.to_tensor(np.ones((4,), np.float32))
            paddle.add(x, 1.0).numpy()

        rep = report(step, warmup=1)
        assert rep.capture is not None and rep.capture.flushes
        assert rep.lint is not None and rep.lint.files_scanned > 100
        text = rep.render()
        assert "capture report" in text and "lint:" in text
        d = rep.to_dict()
        assert "capture" in d and "lint" in d

    def test_self_check_passes(self):
        out = self_check()
        assert out["ok"], out

    def test_cli_rules_and_main(self, capsys):
        from paddle_tpu.analysis.__main__ import main
        assert main(["--rules"]) == 0
        out = capsys.readouterr().out
        for rid in RULES:
            assert rid in out

    def test_analysis_metrics_registered(self):
        from paddle_tpu.observability import metrics as om
        snap = om.snapshot()
        assert "analysis" in snap
        assert snap["analysis"].get("audits_total", 0) >= 1
