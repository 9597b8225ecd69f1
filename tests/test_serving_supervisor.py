"""Self-healing serving plane (ISSUE 15): supervised decode loop with
crash recovery, adaptive admission, and canary rollout.

Chaos contract pinned here: with a KillPoint crashing the decode loop
mid-decode under concurrent submits, the supervisor restarts the loop
and every accepted request ends with exactly ONE terminal flight
event; recovered greedy streams are BIT-equal to an uninterrupted
oracle (committed tokens are durable host state — recovery re-prefills
``prompt + committed`` through the normal admission path); a request
active at two consecutive crashes is quarantined (reason=poison)
instead of crash-looping the replica; the adaptive policy brownouts
(spec window, then prefill chunk) BEFORE any hard shed and releases
when pressure clears; and a divergent checkpoint rolled onto a canary
is auto-rolled-back bit-equal while the rollout halts.

Cost discipline: the oracle streams are memoized (``greedy_ref``,
tests/conftest.py), most chaos mechanics run on jax-free fake engines (the
test_flight FakeEngine pattern, made causal-LM-faithful: the next
token is a pure function of the WHOLE sequence so far, so re-prefill
resumes exactly like the real engines), and only the bit-equality
chaos test and the rollout test touch compiled engines.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import flight
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine
from paddle_tpu.serving_cache import PagedKVCache
from paddle_tpu.serving_supervisor import (AdaptiveAdmissionPolicy,
                                           RolloutPolicy,
                                           ServingSupervisor,
                                           StaticShedPolicy,
                                           default_policy, rollout,
                                           supervise)
from paddle_tpu.utils import fault_injection as fi

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, use_flash_attention=False)

TERMINAL = {"finished", "expired", "failed"}


def _reg():
    return obs.default_registry()


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


@pytest.fixture(scope="module")
def model_b():
    paddle.seed(23)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


@pytest.fixture(scope="module")
def paged64(model):
    """Shared paged engine; tests reset it to pristine afterwards."""
    return PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                  block_size=8, prefill_chunk=8)


@pytest.fixture()
def dump_dir(tmp_path):
    prev = paddle.get_flags("FLAGS_flight_dump_dir")
    paddle.set_flags({"FLAGS_flight_dump_dir": str(tmp_path)})
    try:
        yield str(tmp_path)
    finally:
        paddle.set_flags(prev)


@pytest.fixture(autouse=True)
def quiet_thread_hook():
    """The seeded KillPoints die through threading.excepthook; keep the
    default traceback spew out of the test log."""
    prev = threading.excepthook
    threading.excepthook = lambda args: None
    try:
        yield
    finally:
        threading.excepthook = prev
        fi.clear()


class FakeCausalEngine:
    """jax-free duck-typed engine whose next token is a pure function
    of the WHOLE token sequence so far — a request re-admitted with
    prompt + committed tokens therefore resumes exactly like the real
    causal engine, which is the property crash recovery leans on. It
    sits over a REAL PagedKVCache (pure host), so the
    adaptive-admission evidence (blocks_free/reservations) and the
    server's path (begin_request/prefill_chunk/defer) are all genuine
    — without a single compile. ``num_blocks=None`` sizes the pool so
    that admission never defers."""

    def __init__(self, slots=2, max_seq=64, step_sleep=0.0, block_size=8,
                 num_blocks=None):
        self.max_slots, self.max_seq, self.eos_id = slots, max_seq, None
        self.step_sleep = step_sleep
        self.active = np.zeros(slots, bool)
        self.pos = np.zeros(slots, np.int64)
        self._seq = {}
        self._kv = PagedKVCache(
            max_slots=slots, max_seq=max_seq, block_size=block_size,
            num_blocks=num_blocks or slots * -(-max_seq // block_size))
        self._prefill_state = {}
        self._spec_suppressed = False
        self._chunk_cap = None

    @staticmethod
    def _next(seq):
        return (sum(seq) * 7 + len(seq)) % 997

    def spec_ready(self):
        return False  # no draft on the fake

    def begin_request(self, slot, prompt, budget):
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        total = min(len(prompt) + max(int(budget), 1), self.max_seq)
        if not self._kv.admit(slot, len(prompt), total):
            return False
        self._prefill_state[slot] = prompt
        self.active[slot] = False
        return True

    def prefill_chunk(self, slot):
        seq = self._prefill_state.pop(slot)
        tok = self._next(seq)
        self._seq[slot] = seq + [tok]
        self.pos[slot] = len(seq)
        self.active[slot] = True
        return tok

    def step(self):
        if self.step_sleep:
            time.sleep(self.step_sleep)
        out = np.zeros(self.max_slots, np.int64)
        for s in range(self.max_slots):
            if self.active[s]:
                self._kv.ensure_token(s, int(self.pos[s]))
                tok = self._next(self._seq[s])
                self._seq[s].append(tok)
                self.pos[s] += 1
                out[s] = tok
        return out

    def release(self, slot, evicted=False):
        self.active[slot] = False
        self.pos[slot] = 0
        self._seq.pop(slot, None)
        self._prefill_state.pop(slot, None)
        self._kv.release(slot, evicted=evicted)

    def reset_state(self):
        for s in range(self.max_slots):
            self._kv.release(s, evicted=True)
        self._prefill_state.clear()
        self.active[:] = False
        self.pos[:] = 0
        self._seq.clear()


def _terminal_counts(trace_ids):
    evs = flight.events(category="serving")
    return {tid: sum(1 for e in evs
                     if e.get("trace_id") == tid
                     and e["name"] in TERMINAL)
            for tid in trace_ids}


# ---------------------------------------------------------------------------
# crash recovery
# ---------------------------------------------------------------------------

class TestCrashRecovery:
    def test_chaos_killpoint_recovers_bit_equal(self, model, greedy_ref,
                                                paged64, dump_dir):
        """The acceptance chaos scenario on the REAL paged engine:
        KillPoint mid-decode under concurrent submits — the supervisor
        dumps, restarts, and every stream finishes BIT-equal to the
        uninterrupted oracle with exactly one terminal flight event."""
        flight.clear()
        srv = GenerationServer(paged64)
        sup = supervise(srv, backoff=0.01)
        reqs = []
        try:
            # the 3rd decode passage dies: victims are mid-stream with
            # committed tokens (and, with 2 slots x 3 requests, one
            # request is still queued — untouched by the crash)
            fi.inject("serving.decode", kill=True, skip=2)
            for prompt, n in (([5, 9, 11], 7), ([2, 4], 6),
                              ([7, 1, 3, 8], 5)):
                reqs.append((srv.submit(prompt, max_new_tokens=n),
                             prompt, n))
            for req, prompt, n in reqs:
                assert req["done"].wait(60), srv.stats()
                assert req["error"] is None
                assert list(req["out"]) == greedy_ref(prompt, n)
            assert sup.restarts == 1
            assert sup.recovered >= 1 and sup.quarantined == 0
            counts = _terminal_counts([r["trace_id"]
                                       for r, _, _ in reqs])
            assert all(c == 1 for c in counts.values()), counts
            # the supervisor journaled the death + recovery + restart
            names = [e["name"]
                     for e in flight.events(category="supervisor")]
            assert "loop_death" in names and "restart" in names
            assert "recover" in names
            # and auto-dumped forensics
            assert flight.find_dumps(dump_dir)
            # the replica is healthy: pool pristine, a fresh request
            # serves the oracle stream
            assert srv.generate([6, 2], max_new_tokens=4,
                                timeout=60) == greedy_ref([6, 2], 4)
        finally:
            fi.clear("serving.decode")
            sup.stop()
            srv.shutdown(timeout=10)
            paged64.reset_state()
        st = paged64._kv.stats()
        assert st["blocks_used"] == 0 and st["blocks_reserved"] == 0

    def test_quarantine_repeat_offender(self, dump_dir):
        """A request active at two consecutive crashes is failed
        (reason=poison) instead of re-admitted a third time; the loop
        stays up for everyone else."""
        flight.clear()
        srv = GenerationServer(FakeCausalEngine())
        sup = supervise(srv, backoff=0.01, quarantine_after=2)
        try:
            fi.inject("serving.decode", kill=True, times=2, skip=1)
            req = srv.submit([5, 6], max_new_tokens=20)
            assert req["done"].wait(30)
            assert isinstance(req["error"], RuntimeError)
            assert "poison" in str(req["error"])
            # the quarantine verdict lands BEFORE the backoff+restart;
            # give the second restart its beat to complete
            deadline = time.monotonic() + 10
            while sup.restarts < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert sup.quarantined == 1 and sup.restarts == 2
            quar = [e for e in flight.events(category="supervisor")
                    if e["name"] == "quarantine"]
            assert quar and quar[-1]["attrs"]["reason"] == "poison"
            assert quar[-1]["trace_id"] == req["trace_id"]
            # exactly ONE terminal event, and it is the failure
            assert _terminal_counts([req["trace_id"]]) \
                == {req["trace_id"]: 1}
            assert srv.stats()["quarantined"] == 1
            # the replica survives its poison input
            assert len(srv.generate([7], max_new_tokens=3,
                                    timeout=30)) == 3
        finally:
            sup.stop()
            srv.shutdown(timeout=10)

    def test_backoff_grows_and_gives_up(self, dump_dir):
        """Every decode passage dies: restarts back off exponentially
        and the supervisor eventually fails everything pending instead
        of spinning forever."""
        flight.clear()
        srv = GenerationServer(FakeCausalEngine())
        sup = supervise(srv, backoff=0.005, backoff_cap=0.02,
                        max_restarts=3, quarantine_after=99)
        try:
            fi.inject("serving.decode", kill=True, times=100)
            req = srv.submit([3], max_new_tokens=5)
            assert req["done"].wait(30)
            assert isinstance(req["error"], RuntimeError)
            assert "gave up" in str(req["error"])
            assert sup.gave_up and sup.restarts == 3
            assert any(e["name"] == "give_up"
                       for e in flight.events(category="supervisor"))
            # a given-up server stops its intake: later submissions
            # reject FAST instead of queueing for a loop that will
            # never drain them, and shutdown returns immediately
            with pytest.raises(RuntimeError, match="shutting down"):
                srv.submit([1], max_new_tokens=2)
            assert srv.shutdown(timeout=5)
        finally:
            fi.clear("serving.decode")
            sup.stop()

    def test_double_recovery_stays_bit_equal(self, dump_dir):
        """With a quarantine threshold above 2, a request recovered
        TWICE must still resume bit-equal — only the not-yet-folded
        committed tokens join the prompt at each recovery (re-folding
        would duplicate the stream)."""
        srv = GenerationServer(FakeCausalEngine())
        sup = supervise(srv, backoff=0.01, quarantine_after=3)
        try:
            # two kills from one arm: passages 1-2 clean (tokens
            # commit), passage 3 dies, and the recovered loop's first
            # decode passage dies again — so recovery #2 must fold
            # ONLY the tokens committed since recovery #1
            fi.inject("serving.decode", kill=True, times=2, skip=2)
            req = srv.submit([8, 3], max_new_tokens=10)
            assert req["done"].wait(30)
            assert req["error"] is None
            oracle = GenerationServer(FakeCausalEngine())
            want = oracle.generate([8, 3], max_new_tokens=10,
                                   timeout=30)
            oracle.shutdown()
            assert list(req["out"]) == want
            assert sup.restarts == 2 and sup.quarantined == 0
        finally:
            fi.clear("serving.decode")
            sup.stop()
            srv.shutdown(timeout=10)

    def test_stall_watchdog_fences_and_recovers(self, dump_dir):
        """A decode loop that is alive but wedged (heartbeat stale
        while holding work) is fenced and replaced; the wedged zombie
        exits through the epoch fence when it finally wakes, and the
        request resumes bit-equal."""
        flight.clear()

        class StallEngine(FakeCausalEngine):
            def __init__(self):
                super().__init__()
                self.gate = threading.Event()
                self.calls = 0

            def step(self):
                self.calls += 1
                if self.calls == 3:
                    self.gate.wait(30)  # the stall (zombie parks here)
                return super().step()

        eng = StallEngine()
        srv = GenerationServer(eng)
        sup = supervise(srv, backoff=0.01, stall_seconds=0.15,
                        poll=0.02)
        try:
            req = srv.submit([4, 2], max_new_tokens=8)
            assert req["done"].wait(30)
            assert req["error"] is None
            oracle = GenerationServer(FakeCausalEngine())
            want = oracle.generate([4, 2], max_new_tokens=8, timeout=30)
            oracle.shutdown()
            assert list(req["out"]) == want
            assert sup.stalls == 1 and sup.restarts == 1
            assert srv.stats()["loop_restarts"] == 1
        finally:
            eng.gate.set()  # release the zombie; the fence retires it
            sup.stop()
            srv.shutdown(timeout=10)

    def test_gauges_true_after_unsupervised_crash(self, model, paged64):
        """Satellite audit pin: after a KillPoint kills the loop with
        NO supervisor attached, queue_depth/in_flight/blocks_used must
        read the TRUE wreckage (the victim still holds its slot and
        blocks) — not whatever the last completed step boundary wrote
        (the kill lands between admission and the gauge sweep)."""
        flight.clear()
        srv = GenerationServer(paged64)
        try:
            fi.inject("serving.decode", kill=True)  # first passage
            req = srv.submit([9, 8, 7], max_new_tokens=6)
            deadline = time.monotonic() + 30
            while srv._thread.is_alive() \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not srv._thread.is_alive()
            assert srv.stats()["crashed"] == 1
            assert not req["done"].is_set()  # died mid-flight, no
            # terminal event before recovery (none is coming)
            g = _reg()
            assert g.get("serving.in_flight").value() == 1.0
            assert g.get("serving.queue_depth").value() == 0.0
            assert g.get("serving.blocks_used").value() > 0
            crashes = [e for e in flight.events(category="serving")
                       if e["name"] == "loop_crashed"]
            assert crashes \
                and crashes[-1]["attrs"]["error"] == "KillPoint"
        finally:
            fi.clear("serving.decode")
            srv.shutdown(drain=False, timeout=0.5)
            paged64.reset_state()


# ---------------------------------------------------------------------------
# adaptive admission
# ---------------------------------------------------------------------------

class TestAdaptiveAdmission:
    def test_default_policy_follows_flag(self):
        assert isinstance(default_policy(), StaticShedPolicy)
        paddle.set_flags(
            {"FLAGS_serving_admission_policy": "adaptive"})
        try:
            assert isinstance(default_policy(),
                              AdaptiveAdmissionPolicy)
        finally:
            paddle.set_flags(
                {"FLAGS_serving_admission_policy": "static"})

    def test_brownout_staircase_before_shed_and_release(self):
        """Integration under synthetic block starvation + queue
        growth (real PagedKVCache accounting, fake compute): the
        journal shows spec brownout, then prefill brownout, then — and
        only then — a hard shed; counted; and admission releases once
        pressure clears."""
        flight.clear()
        policy = AdaptiveAdmissionPolicy(alpha=0.9, starve_frac=0.4,
                                         queue_bound=1)
        # pool of 8 blocks: the first request reserves 6, leaving 2
        # (starved at the 0.4 threshold but NOT exhausted — shedding
        # engages before the pool runs dry), the second defers, the
        # rest queue behind it
        eng = FakeCausalEngine(num_blocks=8, step_sleep=0.002)
        srv = GenerationServer(eng, policy=policy)
        try:
            a = srv.submit([1, 2, 3, 4], max_new_tokens=40)
            b = srv.submit([5, 6, 7, 8], max_new_tokens=40)
            c = srv.submit([9], max_new_tokens=3)
            # pressure builds one level per step boundary
            deadline = time.monotonic() + 30
            while policy.level < 3 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert policy.level == 3, policy.journal()
            assert eng._spec_suppressed and eng._chunk_cap == 8
            shed0 = srv.stats()["shed"]
            with pytest.raises(RuntimeError, match="shed"):
                srv.submit([4], max_new_tokens=2)
            assert srv.stats()["shed"] == shed0 + 1
            events = [j["event"] for j in policy.journal()]
            assert "shed" in events
            order = [events.index("engage_brownout_spec"),
                     events.index("engage_brownout_prefill"),
                     events.index("engage_shed")]
            assert order == sorted(order), events
            # brownout engaged strictly before the hard rejection
            assert events.index("engage_brownout_spec") \
                < events.index("shed")
            assert [e for e in flight.events(category="admission")]
            # drain: once every stream completes and the pool clears,
            # admission releases and a fresh request is served
            for req in (a, b, c):
                assert req["done"].wait(60)
                assert req["error"] is None
            out = srv.generate([3, 3], max_new_tokens=2, timeout=30)
            assert len(out) == 2
            assert policy.level == 0
            assert any(e.startswith("release_")
                       for e in [j["event"] for j in policy.journal()])
            assert not eng._spec_suppressed and eng._chunk_cap is None
        finally:
            srv.shutdown(timeout=10)

    def test_deadline_aware_rejection_at_submit(self):
        """A request whose deadline cannot be met at the observed
        steps/sec is rejected at SUBMIT (counted + journaled), before
        it burns blocks; a meetable one is admitted."""
        flight.clear()
        policy = AdaptiveAdmissionPolicy(alpha=0.9, min_steps=3)
        eng = FakeCausalEngine(num_blocks=32, step_sleep=0.02)
        srv = GenerationServer(eng, policy=policy)
        try:
            # warm the throughput EWMA with a real stream (~50 tok/s
            # per request at the fake's 0.02s step)
            srv.generate([1, 2], max_new_tokens=8, timeout=30)
            assert policy._ewma_rps is not None
            r0 = _reg().get(
                "serving.admission_deadline_rejected_total").value()
            with pytest.raises(RuntimeError, match="deadline"):
                srv.submit([1], max_new_tokens=10_000, deadline=0.5)
            assert _reg().get(
                "serving.admission_deadline_rejected_total").value() \
                == r0 + 1
            assert srv.stats()["deadline_rejected"] == 1
            assert any(j["event"] == "deadline_reject"
                       for j in policy.journal())
            # plenty of deadline: admitted and served
            out = srv.generate([1], max_new_tokens=2, timeout=30,
                               deadline=60.0)
            assert len(out) == 2
        finally:
            srv.shutdown(timeout=10)

    def test_static_policy_unchanged_behavior(self):
        """The default policy is the static flag rule: no brownout
        state, no deadline rejection — deadline-bound requests expire
        (post-admission) exactly as before."""
        srv = GenerationServer(FakeCausalEngine(step_sleep=0.01))
        try:
            assert isinstance(srv.policy, StaticShedPolicy)
            req = srv.submit([1], max_new_tokens=1000, deadline=0.05)
            assert req["done"].wait(30)
            assert isinstance(req["error"], TimeoutError)
        finally:
            srv.shutdown(timeout=10)


# ---------------------------------------------------------------------------
# canary rollout
# ---------------------------------------------------------------------------

class TestCanaryRollout:
    @staticmethod
    def _fleet(model, n=2):
        servers = []
        for _ in range(n):
            eng = PagedLlamaDecodeEngine(model, max_slots=1,
                                         max_seq=64, block_size=8,
                                         prefill_chunk=8)
            servers.append(GenerationServer(eng))
        return servers

    @staticmethod
    def _sd(model):
        return {k: v for k, v in model.named_parameters()}

    def test_good_checkpoint_rolls_everywhere_and_bad_rolls_back(
            self, model, model_b):
        """One fleet, three deploys: identical weights proceed across
        every replica (zero probe divergence); a divergent checkpoint
        trips the canary probe and is auto-rolled-back BIT-equal with
        the rollout halted (replica 2 never touched); a NaN-poisoned
        checkpoint is stopped by the finite-weights gate before ANY
        replica swaps."""
        flight.clear()
        servers = self._fleet(model)
        pol = RolloutPolicy(probe_prompt=[1, 2, 3], probe_tokens=5,
                            max_divergence=0.0)
        try:
            baseline = servers[0].generate([1, 2, 3], 5, timeout=60)
            # -- good: same weights, divergence 0, full fleet
            rep = rollout(self._sd(model), servers, pol)
            assert rep["swapped"] == 2 and not rep["halted"]
            assert rep["stages"][0]["divergence"] == 0.0
            assert servers[0].stats()["weight_swaps"] == 1
            # -- divergent: canary rolls back, fleet untouched
            before_1 = servers[1].engine.params
            rolled = _reg().get(
                "serving.rollout_rollbacks_total").value()
            rep = rollout(self._sd(model_b), servers, pol)
            assert rep["halted"] and rep["rolled_back"] == 1
            assert rep["reason"] == "probe_divergence"
            assert rep["stages"][0]["divergence"] > 0.0
            assert servers[1].engine.params is before_1
            assert _reg().get(
                "serving.rollout_rollbacks_total").value() \
                == rolled + 1
            # pre-swap streams restored bit-equal on the canary
            assert servers[0].generate([1, 2, 3], 5,
                                       timeout=60) == baseline
            names = [e["name"]
                     for e in flight.events(category="rollout")]
            assert "canary_probe" in names and "rollback" in names
            # -- NaN: the finite gate halts before any swap
            sd = self._sd(model)
            bad = {k: (v * float("nan") if k == "llama.norm.weight"
                       else v) for k, v in sd.items()}
            nf0 = _reg().get(
                "serving.rollout_nonfinite_weights_total").value()
            rep = rollout(bad, servers, pol)
            assert rep["halted"] and rep["swapped"] == 0
            assert rep["reason"] == "nonfinite_weights"
            assert _reg().get(
                "serving.rollout_nonfinite_weights_total").value() \
                > nf0
            assert servers[0].generate([1, 2, 3], 5,
                                       timeout=60) == baseline
        finally:
            for srv in servers:
                srv.shutdown(timeout=10)
