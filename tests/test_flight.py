"""Flight recorder: ring semantics, dump triggers (explicit /
unhandled exception / watchdog timeout), the per-request serving
lifecycle trail, crash forensics for a kill-point mid-decode, and the
gauge-vs-journal consistency contract (ISSUE 8)."""
from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import flight
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving_cache import PagedKVCache
from paddle_tpu.utils import fault_injection as fi


@pytest.fixture()
def dump_dir(tmp_path):
    """Route dumps into the test's tmp dir; restore afterwards."""
    prev = paddle.get_flags("FLAGS_flight_dump_dir")
    paddle.set_flags({"FLAGS_flight_dump_dir": str(tmp_path)})
    try:
        yield str(tmp_path)
    finally:
        paddle.set_flags(prev)


@pytest.fixture()
def quiet_thread_hook():
    """Install the crash hooks with the default traceback print
    silenced (the crashes below are seeded); uninstall afterwards."""
    prev = threading.excepthook
    threading.excepthook = lambda args: None
    flight.install_crash_hooks()
    try:
        yield
    finally:
        flight.uninstall_crash_hooks()
        threading.excepthook = prev


class FakeEngine:
    """Duck-typed decode engine: just enough surface for
    GenerationServer's host orchestration (begin_request /
    prefill_chunk / step / release over a real, pure-host
    PagedKVCache), no jax."""

    def __init__(self, slots=2, step_sleep=0.0):
        self.max_slots = slots
        self.max_seq = 64
        self.eos_id = None
        self.step_sleep = step_sleep
        self.pos = np.zeros(slots, np.int32)
        self.active = np.zeros(slots, bool)
        self._kv = PagedKVCache(max_slots=slots, max_seq=self.max_seq,
                                block_size=8, num_blocks=8 * slots)
        self._staged = {}

    def spec_ready(self):
        return False

    def begin_request(self, slot, ids, budget):
        total = min(len(ids) + max(int(budget), 1), self.max_seq)
        if not self._kv.admit(slot, len(ids), total):
            return False
        self._staged[slot] = len(ids)
        return True

    def prefill_chunk(self, slot):
        self.pos[slot] = self._staged.pop(slot)
        self.active[slot] = True
        return 7

    def step(self):
        if self.step_sleep:
            time.sleep(self.step_sleep)
        out = np.zeros(self.max_slots, np.int64)
        for s in range(self.max_slots):
            if self.active[s]:
                self._kv.ensure_token(s, int(self.pos[s]))
                self.pos[s] += 1
                out[s] = 100 + s
        return out

    def release(self, slot, evicted=False):
        self.active[slot] = False
        self.pos[slot] = 0
        self._staged.pop(slot, None)
        self._kv.release(slot, evicted=evicted)


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

class TestRing:
    def test_record_and_fields(self):
        flight.clear()
        flight.record("t", "ev", trace_id="abc", n=3)
        (e,) = flight.events(category="t")
        assert e["cat"] == "t" and e["name"] == "ev"
        assert e["trace_id"] == "abc" and e["attrs"] == {"n": 3}
        assert e["thread"] == threading.current_thread().name
        assert e["ts_us"] > 0

    def test_kill_switch(self):
        flight.clear()
        paddle.set_flags({"FLAGS_flight_recorder": 0})
        try:
            flight.record("t", "dropped")
            assert flight.events(category="t") == []
        finally:
            paddle.set_flags({"FLAGS_flight_recorder": 1})
        flight.record("t", "kept")
        assert [e["name"] for e in flight.events(category="t")] == ["kept"]

    def test_capacity_eviction_and_dropped(self):
        prev = paddle.get_flags("FLAGS_flight_recorder_capacity")
        try:
            paddle.set_flags({"FLAGS_flight_recorder_capacity": 32})
            flight.clear()
            for i in range(100):
                flight.record("t", "e", i=i)
            evs = flight.events(category="t")
            assert len(evs) == 32
            # the LAST 32 survive (a black box keeps the newest tail)
            assert [e["attrs"]["i"] for e in evs] == list(range(68, 100))
            assert flight.dropped() == 100 - 32
            assert flight.appended() == 100
        finally:
            paddle.set_flags(prev)
            flight.clear()

    def test_trace_and_last_n_filters(self):
        flight.clear()
        for i in range(6):
            flight.record("t", "e", trace_id=f"r{i % 2}", i=i)
        r0 = flight.events(trace_id="r0")
        assert [e["attrs"]["i"] for e in r0] == [0, 2, 4]
        assert len(flight.events(n=2, category="t")) == 2

    def test_chrome_events_shape(self):
        flight.clear()
        flight.record("t", "mark", trace_id="x", k=1)
        ev = next(e for e in flight.chrome_events()
                  if e["name"] == "t.mark")
        assert ev["ph"] == "i"
        assert ev["args"] == {"k": 1, "trace_id": "x"}


# ---------------------------------------------------------------------------
# dumps
# ---------------------------------------------------------------------------

class TestDump:
    def test_explicit_dump_roundtrip(self, dump_dir):
        flight.clear()
        flight.record("t", "one", trace_id="tr", a=1)
        flight.record("t", "two")
        before = obs.default_registry().get(
            "observability.dumps_total").value(trigger="explicit")
        path = flight.dump(trigger="explicit", note="unit")
        assert path.startswith(dump_dir)
        assert flight.last_dump_path() == path
        header, evs = flight.load_dump(path)
        assert header["kind"] == "flight_header"
        assert header["trigger"] == "explicit"
        assert header["note"] == "unit"
        assert header["events"] == len(evs)
        names = [e["name"] for e in evs if e["cat"] == "t"]
        assert names == ["one", "two"]
        tr = [e for e in evs if e.get("trace_id") == "tr"]
        assert tr and tr[0]["attrs"] == {"a": 1}
        after = obs.default_registry().get(
            "observability.dumps_total").value(trigger="explicit")
        assert after == before + 1
        # every line of the dump is standalone JSON (forensics greppable)
        with open(path) as f:
            for line in f:
                json.loads(line)
        # rendering never crashes and names the trigger
        text = flight.render_events(evs, header)
        assert "trigger=explicit" in text and "t.one" in text

    def test_dump_works_with_recorder_off(self, dump_dir):
        flight.clear()
        flight.record("t", "pre")
        paddle.set_flags({"FLAGS_flight_recorder": 0})
        try:
            _, evs = flight.load_dump(flight.dump())
        finally:
            paddle.set_flags({"FLAGS_flight_recorder": 1})
        assert any(e["name"] == "pre" for e in evs)

    def test_find_dumps_newest_first(self, dump_dir):
        p1 = flight.dump(trigger="explicit")
        time.sleep(0.02)
        p2 = flight.dump(trigger="explicit")
        found = flight.find_dumps(dump_dir)
        assert found[0] == p2 and p1 in found

    def test_cli_renders_dump(self, dump_dir, capsys):
        flight.clear()
        flight.record("cli", "seeded", trace_id="cli-1")
        flight.record("cli", "other", trace_id="cli-2")
        path = flight.dump()
        from paddle_tpu.observability.__main__ import main
        assert main(["--flight", path]) == 0
        out = capsys.readouterr().out
        assert "cli.seeded" in out and "[cli-1]" in out
        # --trace filters to one request's trail
        assert main(["--flight", path, "--trace", "cli-1",
                     "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert {e["trace_id"] for e in data["events"]} == {"cli-1"}


# ---------------------------------------------------------------------------
# crash triggers
# ---------------------------------------------------------------------------

class TestCrashHooks:
    def test_thread_crash_dumps(self, dump_dir, quiet_thread_hook):
        flight.clear()
        flight.record("t", "before_crash", probe=7)

        def boom():
            raise RuntimeError("seeded thread crash")

        t = threading.Thread(target=boom)
        t.start()
        t.join()
        dumps = flight.find_dumps(dump_dir)
        assert dumps, "thread crash left no flight dump"
        header, evs = flight.load_dump(dumps[0])
        assert header["trigger"] == "exception"
        assert any(e["name"] == "before_crash" for e in evs)
        crash = [e for e in evs if e["cat"] == "crash"]
        assert crash and crash[-1]["attrs"]["error"] == "RuntimeError"

    def test_sys_excepthook_wrapper_dumps(self, dump_dir,
                                          quiet_thread_hook):
        import sys
        flight.clear()
        flight.record("t", "mainline_state")
        try:
            raise ValueError("seeded main-thread crash")
        except ValueError:
            tp, val, tb = sys.exc_info()
        prev_sys = sys.__excepthook__  # silence the chained print
        try:
            sys.__excepthook__ = lambda *a: None
            # call the installed wrapper directly (raising through the
            # real top-level would kill pytest); chaining is part of
            # the contract and must not raise
            sys.excepthook(tp, val, tb)
        finally:
            sys.__excepthook__ = prev_sys
        dumps = flight.find_dumps(dump_dir)
        assert dumps
        header, evs = flight.load_dump(dumps[0])
        assert header["trigger"] == "exception"
        assert any(e["name"] == "mainline_state" for e in evs)

    def test_uninstall_restores_hooks(self):
        import sys
        prev_sys, prev_thr = sys.excepthook, threading.excepthook
        flight.install_crash_hooks()
        assert sys.excepthook is not prev_sys
        flight.uninstall_crash_hooks()
        assert sys.excepthook is prev_sys
        assert threading.excepthook is prev_thr


class TestWatchdogDump:
    def test_timeout_leaves_forensics(self, dump_dir):
        """A hung step doesn't just bump timeouts_total: it freezes the
        black box (ISSUE 8 satellite: hung collective -> forensics)."""
        from paddle_tpu.distributed.watchdog import (Watchdog,
                                                     WatchdogTimeout)
        flight.clear()
        flight.record("t", "pre_hang_state")
        before = obs.default_registry().get(
            "observability.dumps_total").value(trigger="watchdog")
        release = threading.Event()
        wd = Watchdog(timeout=0.2)
        with pytest.raises(WatchdogTimeout):
            wd.run(release.wait, 30.0)
        release.set()  # unblock the worker thread
        after = obs.default_registry().get(
            "observability.dumps_total").value(trigger="watchdog")
        assert after == before + 1
        dumps = flight.find_dumps(dump_dir)
        assert dumps
        header, evs = flight.load_dump(dumps[0])
        assert header["trigger"] == "watchdog"
        wd_evs = [e for e in evs if e["cat"] == "watchdog"]
        assert wd_evs and wd_evs[-1]["name"] == "timeout"
        assert any(e["name"] == "pre_hang_state" for e in evs)


class TestSelfCheckIntegration:
    def test_flight_self_check_robust_to_live_env(self):
        """report.self_check must pass with production crash hooks
        already installed AND the operator's recorder kill switch off —
        and must take its synthetic crash back out of the ring."""
        import signal

        from paddle_tpu.analysis.report import self_check
        prev_flag = paddle.get_flags("FLAGS_flight_recorder")
        prev_thr = threading.excepthook
        # the documented production setup, incl. a live-dump signal
        flight.install_crash_hooks(signals=(signal.SIGUSR1,))
        try:
            paddle.set_flags({"FLAGS_flight_recorder": 0})
            out = self_check()
            assert out["checks"]["flight"] is True, out["detail"]
            # the operator's kill-switch choice survives the check
            assert not paddle.get_flags(
                "FLAGS_flight_recorder")["FLAGS_flight_recorder"]
            # production hooks are back in place, state consistent
            assert flight._hooks_installed
            # the SIGUSR1 live-dump trigger survives too (SIG_DFL for
            # SIGUSR1 would TERMINATE the process on the next signal)
            assert signal.getsignal(signal.SIGUSR1) \
                is not signal.SIG_DFL
            assert signal.SIGUSR1 in flight._prev_signals
            # no synthetic residue pollutes later REAL dumps
            assert flight.events(category="selfcheck") == []
            assert not any(
                "self-check seeded" in str(e.get("attrs", {}))
                for e in flight.events(category="crash"))
        finally:
            paddle.set_flags(prev_flag)
            flight.uninstall_crash_hooks()
            threading.excepthook = prev_thr


class TestChromeMerge:
    def test_flight_events_land_in_chrome_export(self, tmp_path):
        """export_chrome_tracing carries all three planes: spans,
        step-timeline counters, and the flight trail as instant marks."""
        from paddle_tpu import profiler
        if profiler._lib is None:
            pytest.skip("native tracer unavailable")
        flight.clear()
        flight.record("merge", "probe", trace_id="m-1", k=2)
        path = str(tmp_path / "trace.json")
        profiler.export_chrome_tracing(path)
        with open(path) as f:
            data = json.load(f)
        marks = [e for e in data.get("traceEvents", [])
                 if e.get("name") == "merge.probe"]
        assert marks, "flight event missing from the merged trace"
        assert marks[0]["ph"] == "i"
        assert marks[0]["args"] == {"k": 2, "trace_id": "m-1"}


# ---------------------------------------------------------------------------
# serving lifecycle trail
# ---------------------------------------------------------------------------

class TestServingLifecycle:
    def test_full_trail_in_order(self):
        flight.clear()
        q0 = obs.default_registry().get(
            "serving.queue_seconds").value()["count"]
        d0 = obs.default_registry().get(
            "serving.decode_seconds").value()["count"]
        srv = GenerationServer(FakeEngine())
        try:
            req = srv.submit([1, 2, 3], max_new_tokens=3)
            assert req["done"].wait(30)
            trail = srv.trace(req)  # req dict and trace_id both work
            assert trail == srv.trace(req["trace_id"])
            names = [e["name"] for e in trail]
            assert names[:5] == ["submit", "queued", "admitted",
                                 "prefill_chunk", "prefilled"]
            assert names[-1] == "finished"
            assert names[5:-1] == ["decode"] * (len(names) - 6)
            assert trail[-1]["attrs"]["tokens"] == 3
            # decode steps carry a monotone token count
            toks = [e["attrs"]["tokens"] for e in trail
                    if e["name"] == "decode"]
            assert toks == sorted(toks)
            # latency split landed: one queue + one decode observation
            assert obs.default_registry().get(
                "serving.queue_seconds").value()["count"] == q0 + 1
            assert obs.default_registry().get(
                "serving.decode_seconds").value()["count"] == d0 + 1
        finally:
            srv.shutdown()

    def test_rejected_submission_is_journaled(self):
        flight.clear()
        srv = GenerationServer(FakeEngine())
        srv.shutdown()
        with pytest.raises(RuntimeError):
            srv.submit([1], 2)
        evs = flight.events(category="serving")
        assert evs[-1]["name"] == "rejected"
        assert evs[-1]["attrs"]["reason"] == "shutting_down"

    def test_expired_request_is_journaled(self):
        flight.clear()
        q_hist = obs.default_registry().get("serving.queue_seconds")
        q0 = q_hist.value()["count"]
        srv = GenerationServer(FakeEngine(slots=1, step_sleep=0.02))
        try:
            blocker = srv.submit([1, 2], 500)
            starved = srv.submit([3], 8, deadline=0.15)
            assert starved["done"].wait(30)
            assert isinstance(starved["error"], TimeoutError)
            trail = srv.trace(starved)
            assert trail[-1]["name"] == "expired"
            assert trail[-1]["attrs"]["error"] == "TimeoutError"
            # no survivorship bias: the starved (never-admitted) request
            # lands in queue_seconds too — its whole life was queue
            # time — alongside the blocker's admission observation
            assert q_hist.value()["count"] >= q0 + 2
            blocker["expires"] = time.monotonic()  # let shutdown drain
        finally:
            srv.shutdown(timeout=30)

    def test_gauges_agree_with_journal_under_submit_shutdown(self):
        """Concurrent submit + drain shutdown: the queue/in-flight
        gauges must read 0 afterwards and the journal must account for
        every submitted request with exactly one terminal event."""
        flight.clear()
        srv = GenerationServer(FakeEngine(slots=2, step_sleep=0.002))
        reqs, rejected = [], 0
        lock = threading.Lock()

        def submitter(k):
            nonlocal rejected
            for i in range(5):
                try:
                    r = srv.submit([k, i], max_new_tokens=3)
                    with lock:
                        reqs.append(r)
                except RuntimeError:
                    with lock:
                        rejected += 1
                time.sleep(0.001)

        threads = [threading.Thread(target=submitter, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.01)
        assert srv.shutdown(drain=True, timeout=60)
        for t in threads:
            t.join(timeout=30)
        # every accepted request ran to completion (drain contract)
        for r in reqs:
            assert r["done"].is_set()
            assert r["error"] is None
        g = obs.default_registry()
        assert g.get("serving.queue_depth").value() == 0
        assert g.get("serving.in_flight").value() == 0
        # journal cross-check: one terminal event per accepted request,
        # one rejected event per refused submission
        evs = flight.events(category="serving")
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)
        finished = {e["trace_id"] for e in by_name.get("finished", ())}
        assert finished == {r["trace_id"] for r in reqs}
        assert len(by_name.get("rejected", ())) == rejected
        # admitted counter agrees with the journal
        assert srv.admitted == len(by_name.get("admitted", ()))


# ---------------------------------------------------------------------------
# crash forensics: kill-point mid-decode (the acceptance scenario)
# ---------------------------------------------------------------------------

class TestKillPointForensics:
    def test_decode_crash_dump_carries_victim_lifecycle(
            self, dump_dir, quiet_thread_hook):
        """PR 2's KillPoint harness poisons a decode step; the server
        loop thread dies as a real preemption would and the automatic
        exception dump must contain the victim request's COMPLETE
        lifecycle trail under its trace_id."""
        flight.clear()
        srv = GenerationServer(FakeEngine(slots=1))
        victim = None
        try:
            # let two decode passages through, kill the third: the
            # victim is mid-decode with tokens already produced
            fi.inject("serving.decode", kill=True, skip=2)
            victim = srv.submit([1, 2, 3], max_new_tokens=50)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline \
                    and not flight.find_dumps(dump_dir):
                time.sleep(0.01)
            dumps = flight.find_dumps(dump_dir)
            assert dumps, "kill-point crash left no flight dump"
            header, evs = flight.load_dump(dumps[0])
            assert header["trigger"] == "exception"
            tid = victim["trace_id"]
            trail = [e for e in evs if e.get("trace_id") == tid]
            names = [e["name"] for e in trail]
            assert names[:3] == ["submit", "queued", "admitted"]
            assert "decode" in names  # tokens were flowing when it died
            # no terminal event: the request died mid-flight
            assert not ({"finished", "expired", "failed"} & set(names))
            # the crash itself is the journal's closing entry
            crash = [e for e in evs if e["cat"] == "crash"]
            assert crash and crash[-1]["attrs"]["error"] == "KillPoint"
            # the victim never completed
            assert not victim["done"].is_set()
        finally:
            fi.clear("serving.decode")
            srv.shutdown(drain=False, timeout=0.5)

    def test_loop_survives_plain_exception_and_journals_it(self):
        """A non-kill injected fault fails the in-flight requests but
        the loop survives — and the journal says why."""
        flight.clear()
        srv = GenerationServer(FakeEngine(slots=1))
        try:
            fi.inject("serving.decode", times=1)
            req = srv.submit([1, 2], max_new_tokens=5)
            assert req["done"].wait(30)
            assert isinstance(req["error"], fi.InjectedFault)
            trail = srv.trace(req)
            assert trail[-1]["name"] == "failed"
            assert any(e["name"] == "loop_error"
                       for e in flight.events(category="serving"))
            # the loop is still alive: a fresh request serves
            out = srv.generate([5], max_new_tokens=2, timeout=30)
            assert len(out) == 2
        finally:
            fi.clear("serving.decode")
            srv.shutdown()
