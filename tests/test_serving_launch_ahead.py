"""The serving loop keeps one launch ahead of its fetch (ISSUE 31): a pass of
`GenerationServer._loop` enqueues decode launch n+1 and only then fetches
and commits launch n, with the token feedback resident on the device.

What is pinned here, on tiny models on the CPU: the server's streams equal
`engine.generate`'s (the two halves one after the other) token for token,
with an EOS anywhere; the order of the calls an engine sees; what happens to
a launch whose slot was released and taken again while it was in flight; the
places where the loop lands everything first (a weight swap, a shutdown, a
supervisor's restart, a draft attached); and a model with window layers and
counts that ride on the fetches.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import flight
from paddle_tpu.serving import (GenerationServer, PagedLlamaDecodeEngine,
                                _M_overrun)
from paddle_tpu.serving_cache import PagedKVCache
from paddle_tpu.serving_supervisor import supervise
from paddle_tpu.utils import fault_injection as fi

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, use_flash_attention=False)
GEO = dict(max_seq=64, block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def model():
    paddle.seed(31)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


@pytest.fixture(scope="module")
def eng(model):
    """Three slots; every test leaves it with no slot held and no EOS."""
    return PagedLlamaDecodeEngine(model, max_slots=3, **GEO)


@pytest.fixture(scope="module")
def eng1(model):
    """One slot: the next request takes the slot the last one left."""
    return PagedLlamaDecodeEngine(model, max_slots=1, **GEO)


@pytest.fixture(scope="module")
def oracle(model):
    """`engine.generate` of an engine that serves nothing else: prefill
    and `step()`, each launch fetched before the next is enqueued."""
    ref = PagedLlamaDecodeEngine(model, max_slots=1, **GEO)
    streams = {}

    def gen(prompt, n_new, eos=None):
        key = tuple(prompt)
        if len(streams.get(key, ())) < n_new:
            streams[key] = ref.generate(list(prompt), max_new_tokens=n_new)
        out = streams[key][:n_new]
        if eos in out:
            out = out[:out.index(eos) + 1]
        return out

    return gen


@pytest.fixture(autouse=True)
def quiet_thread_hook():
    prev = threading.excepthook
    threading.excepthook = lambda args: None
    try:
        yield
    finally:
        threading.excepthook = prev
        fi.clear()


def _prompt(seed, n):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, CFG["vocab_size"], n)]


def _wait(reqs, seconds=120):
    for r in reqs:
        assert r["done"].wait(seconds), r
        assert r["error"] is None, r["error"]


def _pristine(engine):
    st = engine._kv.stats()
    assert st["blocks_used"] == 0 and st["blocks_reserved"] == 0, st
    assert not engine.active.any() and engine._ahead is None
    assert not engine._first_dev


def _first_at(stream, k):
    """True where `stream[k]` shows up at `k` for the first time: as an
    EOS it ends the request there and nowhere before."""
    return stream[k] not in stream[:k]


# -- (a) streams, lengths, EOS --------------------------------------------------

MIX = [(3, 1), (11, 1), (5, 2), (17, 2), (2, 9), (9, 14), (26, 20), (30, 33),
       (8, 40)]


def test_streams_equal_generate_for_a_mix_of_lengths(eng, oracle):
    """`max_new` of 1, 2 and many over prompts of one to four chunks, nine
    requests over three slots: by counting, a slot leaves the batch with
    its last token launched, and no launch is dropped."""
    srv = GenerationServer(eng)
    over0 = _M_overrun.value()
    try:
        prompts = [_prompt(i, n) for i, (n, _) in enumerate(MIX)]
        reqs = [srv.submit(p, m) for p, (_, m) in zip(prompts, MIX)]
        _wait(reqs)
        for p, (_, m), r in zip(prompts, MIX, reqs):
            assert list(r["out"]) == oracle(p, m), (len(p), m)
        st = srv.stats()
        assert st["tokens_delivered"] == sum(m - 1 for _, m in MIX)
        assert 0 < st["launched_ahead"] < st["steps_run"]
        assert _M_overrun.value() == over0          # no EOS, no deadline
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    _pristine(eng)


def _eos_case(oracle, where, budget=12):
    """A prompt and an EOS id whose first appearance in the prompt's
    stream is at `where` ('first', 'middle', 'last' allowed token)."""
    k = {"first": 0, "middle": budget // 2, "last": budget - 1}[where]
    for seed in range(100, 200):
        p = _prompt(seed, 4 + seed % 9)
        stream = oracle(p, budget)
        if _first_at(stream, k):
            return p, stream[k], k
    raise AssertionError(f"no prompt with a token new at {k}")


@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_an_eos_ends_the_stream_where_generate_ends_it(eng, oracle, where):
    """The EOS is seen one launch late: the stream is `generate`'s all the
    same, and what was launched past it is counted as overrun and reaches
    nobody. A bystander without an EOS in its stream runs through."""
    budget = 12
    p, eos, k = _eos_case(oracle, where, budget)
    other = next(q for q in (_prompt(s, 6) for s in range(300, 400))
                 if eos not in oracle(q, budget + 5))
    eng.eos_id = eos
    srv = GenerationServer(eng)
    over0 = _M_overrun.value()
    try:
        by, req = srv.submit(other, budget + 5), srv.submit(p, budget)
        _wait([by, req])
        assert list(req["out"]) == oracle(p, budget, eos) \
            and len(req["out"]) == k + 1 and req["out"][-1] == eos
        assert list(by["out"]) == oracle(other, budget + 5)
        # its slot had stepped: twice past a first token (the launch of its
        # prompt's pass and the one enqueued before the token's fetch),
        # once past any later one, and never past the last allowed token
        want = {"first": 2, "middle": 1, "last": 0}[where]
        assert _M_overrun.value() - over0 == want
        assert req["launched"] == len(req["out"])
    finally:
        eng.eos_id = None
        assert srv.shutdown(drain=True, timeout=60)
    _pristine(eng)


def test_overrun_counts_the_eos_requests_that_had_a_launch_in_flight(
        eng, oracle):
    """Several at once, an EOS in some: one overrun token a request that
    an EOS ended before its budget did (two where it was its first)."""
    budget = 10
    found = {}
    for seed in range(500, 900):
        p = _prompt(seed, 3 + seed % 11)
        found.setdefault(oracle(p, budget)[4], []).append(p)
    eos, prompts = max(found.items(), key=lambda kv: len(kv[1]))
    prompts = prompts[:4] + [q for t, qs in found.items() if t != eos
                             for q in qs][:3]
    eng.eos_id = eos
    srv = GenerationServer(eng)
    over0 = _M_overrun.value()
    try:
        reqs = [srv.submit(p, budget) for p in prompts]
        _wait(reqs)
        want = 0
        for p, r in zip(prompts, reqs):
            ref = oracle(p, budget, eos)
            assert list(r["out"]) == ref
            if ref[-1] == eos and len(ref) < budget:
                want += 2 if len(ref) == 1 else 1
        assert want >= 4 and _M_overrun.value() - over0 == want
    finally:
        eng.eos_id = None
        assert srv.shutdown(drain=True, timeout=60)
    _pristine(eng)


# -- (b) the order of the calls -------------------------------------------------

class OrderEngine:
    """jax-free engine with the two halves, over a real `PagedKVCache`. The
    next token is a function of the whole sequence, and a launch's tokens
    exist only from `step_enqueue` on, as handles that `step_collect`
    resolves: every call is written down."""

    def __init__(self, slots=2, max_seq=64, collect_sleep=0.0):
        self.max_slots, self.max_seq, self.eos_id = slots, max_seq, None
        self.collect_sleep = collect_sleep
        self.active = np.zeros(slots, bool)
        self.pos = np.zeros(slots, np.int64)
        self._kv = PagedKVCache(max_slots=slots, max_seq=max_seq,
                                block_size=8, num_blocks=slots * 8)
        self._seq, self._staged, self.calls, self._n = {}, {}, [], 0

    @staticmethod
    def _next(seq):
        return (sum(seq) * 7 + len(seq)) % 997

    def spec_ready(self):
        return False

    def begin_request(self, slot, prompt, budget):
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not self._kv.admit(slot, len(prompt),
                              min(len(prompt) + int(budget), self.max_seq)):
            return False
        self._staged[slot] = prompt
        return True

    def prefill_enqueue(self, slot):
        seq = self._staged.pop(slot)
        self._seq[slot] = seq + [self._next(seq)]
        self.pos[slot], self.active[slot] = len(seq), True
        self.calls.append(("prefill_enqueue", slot))
        return {"slot": slot, "tok": self._seq[slot][-1]}

    def prefill_collect(self, first):
        self.calls.append(("prefill_collect", first["slot"]))
        return first["tok"], {}

    def step_enqueue(self):
        self._n += 1
        out = np.zeros(self.max_slots, np.int64)
        for s in np.flatnonzero(self.active):
            self._kv.ensure_token(s, int(self.pos[s]))
            self._seq[s].append(self._next(self._seq[s]))
            self.pos[s] += 1
            out[s] = self._seq[s][-1]
        self.calls.append(("enqueue", self._n))
        return {"n": self._n, "out": out}

    def step_collect(self, launch):
        if self.collect_sleep:
            time.sleep(self.collect_sleep)
        self.calls.append(("collect", launch["n"]))
        return launch["out"], {}

    def leave(self, slot):
        self.active[slot] = False

    def release(self, slot, evicted=False):
        self.active[slot], self.pos[slot] = False, 0
        self._seq.pop(slot, None)
        self._staged.pop(slot, None)
        self._kv.release(slot, evicted=evicted)

    def want(self, prompt, n):
        seq = list(prompt)
        for _ in range(n):
            seq.append(self._next(seq))
        return seq[len(prompt):]


def test_launch_n_plus_1_is_enqueued_before_launch_n_is_collected():
    fake = OrderEngine()
    srv = GenerationServer(fake)
    try:
        a, b = srv.submit([3, 1, 4], 12), srv.submit([1, 5, 9, 2], 7)
        _wait([a, b], 30)
        assert list(a["out"]) == fake.want([3, 1, 4], 12)
        assert list(b["out"]) == fake.want([1, 5, 9, 2], 7)
        at = {c: i for i, c in enumerate(fake.calls)}
        steps = srv.stats()["steps_run"]
        assert steps == fake._n == 11
        for n in range(1, steps):
            assert at[("enqueue", n + 1)] < at[("collect", n)], fake.calls
        # a first token is fetched a pass after its chunk, before the
        # decode launch of that pass
        for slot in (0, 1):
            assert at[("prefill_enqueue", slot)] \
                < at[("prefill_collect", slot)]
        assert at[("prefill_collect", 0)] < at[("collect", 1)]
        # the first launch found nothing in flight, every other did
        assert srv.stats()["launched_ahead"] == steps - 1
    finally:
        assert srv.shutdown(drain=True, timeout=30)


def test_launched_ahead_is_steps_run_less_the_drains():
    """Every time the loop lands everything first (its start, a batch that
    ended, a weight swap) the next launch goes out with nothing ahead."""
    fake = OrderEngine(collect_sleep=0.002)
    fake.swap_weights = lambda prepared=None: None
    srv = GenerationServer(fake)
    try:
        _wait([srv.submit([2, 7], 6)], 30)          # 5 launches, 1 drain
        _wait([srv.submit([1, 8], 4)], 30)          # 3 launches, 1 drain
        busy = srv.submit([2, 8, 1], 40)
        for _ in range(500):
            if len(busy["out"]) >= 5:
                break
            time.sleep(0.002)
        srv.swap_weights(prepared={})               # 1 drain in the middle
        _wait([busy], 30)
        assert list(busy["out"]) == fake.want([2, 8, 1], 40)
        st = srv.stats()
        assert st["steps_run"] == 5 + 3 + 39 and st["weight_swaps"] == 1
        assert st["launched_ahead"] == st["steps_run"] - 4
        # around the swap: the launch in flight was collected before it
        # and the next one enqueued after
        at = {c: i for i, c in enumerate(fake.calls)}
        drained = [n for n in range(10, st["steps_run"])
                   if at[("collect", n)] < at[("enqueue", n + 1)]]
        assert len(drained) == 1
    finally:
        assert srv.shutdown(drain=True, timeout=30)


# -- (c) a slot released and taken again under a launch in flight ---------------

def test_a_slot_taken_again_after_an_eos_gets_none_of_the_old_launch(
        eng1, oracle):
    budget = 12
    p, eos, k = _eos_case(oracle, "middle", budget)
    nxt = next(q for q in (_prompt(s, 7) for s in range(400, 500))
               if eos not in oracle(q, 9))
    eng1.eos_id = eos
    srv = GenerationServer(eng1)
    over0 = _M_overrun.value()
    try:
        first, second = srv.submit(p, budget), srv.submit(nxt, 9)
        _wait([first, second])
        assert list(first["out"]) == oracle(p, budget, eos)
        assert len(first["out"]) == k + 1
        # the one slot went from the first to the second while the first's
        # overrun launch was in flight: its token reached neither
        assert list(second["out"]) == oracle(nxt, 9)
        assert _M_overrun.value() - over0 == 1
        trail = [e["name"] for e in srv.trace(first)]
        assert trail.count("finished") == 1
        assert trail.count("decode") == k
    finally:
        eng1.eos_id = None
        assert srv.shutdown(drain=True, timeout=60)
    _pristine(eng1)


def test_a_slot_taken_again_after_a_deadline_gets_none_of_the_old_launch(
        eng1, oracle):
    collect = eng1.step_collect

    def slow_collect(launch):
        time.sleep(0.03)
        return collect(launch)

    eng1.step_collect = slow_collect
    srv = GenerationServer(eng1)
    over0 = _M_overrun.value()
    try:
        p, nxt = _prompt(41, 5), _prompt(42, 9)
        _wait([srv.submit(p, 2)])                    # compiled before clocks
        late = srv.submit(p, 50, deadline=0.4)
        second = srv.submit(nxt, 8)
        assert late["done"].wait(60) and second["done"].wait(60)
        assert isinstance(late["error"], TimeoutError)
        kept = list(late["out"])
        assert 0 < len(kept) < 50 and kept == oracle(p, 50)[:len(kept)]
        assert second["error"] is None
        assert list(second["out"]) == oracle(nxt, 8)
        # expired under a launch in flight: that token is dropped, not
        # appended after the request's end
        assert _M_overrun.value() - over0 >= 1
        time.sleep(0.1)
        assert list(late["out"]) == kept
    finally:
        del eng1.step_collect
        assert srv.shutdown(drain=True, timeout=60)
    _pristine(eng1)


# -- (d) a swap, a shutdown, a restart with a launch in flight ------------------

def _terminal_counts(reqs):
    evs = flight.events(category="serving")
    return [sum(1 for e in evs if e.get("trace_id") == r["trace_id"]
                and e["name"] in ("finished", "expired", "failed"))
            for r in reqs]


def test_a_weight_swap_lands_the_launch_in_flight_first(eng, oracle, model):
    srv = GenerationServer(eng)
    try:
        prompts = [_prompt(60, 5), _prompt(61, 19)]
        reqs = [srv.submit(p, 30) for p in prompts]
        for _ in range(2000):
            if len(reqs[0]["out"]) >= 4:
                break
            time.sleep(0.002)
        res = srv.swap_weights(model.state_dict())
        assert res["in_flight"] + res["prefilling"] == 2
        # at the boundary nothing was launched that is not in `out`
        _wait(reqs)
        for p, r in zip(prompts, reqs):
            assert list(r["out"]) == oracle(p, 30)
        st = srv.stats()
        assert st["weight_swaps"] == 1
        assert st["tokens_delivered"] == 2 * 29
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    _pristine(eng)


def test_a_draining_shutdown_commits_every_token_once(eng, oracle):
    flight.clear()
    srv = GenerationServer(eng)
    prompts = [_prompt(70 + i, 3 + 5 * i) for i in range(5)]
    reqs = [srv.submit(p, 10 + i) for i, p in enumerate(prompts)]
    for _ in range(2000):
        if srv.steps_run >= 3:
            break
        time.sleep(0.002)
    assert srv.shutdown(drain=True, timeout=120)
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        assert r["done"].is_set() and r["error"] is None
        assert list(r["out"]) == oracle(p, 10 + i)
    assert _terminal_counts(reqs) == [1] * 5
    assert srv.stats()["tokens_delivered"] == sum(9 + i for i in range(5))
    _pristine(eng)


def test_a_restart_commits_nothing_of_the_launch_the_dead_loop_left(
        eng, oracle):
    """A kill with a launch in flight: the dead loop's launch reaches
    nobody, the recovered requests resume from their committed tokens,
    and every stream is `generate`'s with one terminal event."""
    flight.clear()
    srv = GenerationServer(eng)
    sup = supervise(srv, backoff=0.01)
    try:
        fi.inject("serving.decode", kill=True, skip=3)
        work = [(_prompt(80, 4), 9), (_prompt(81, 11), 7),
                (_prompt(82, 3), 8), (_prompt(83, 20), 6)]
        reqs = [srv.submit(p, n) for p, n in work]
        _wait(reqs)
        for (p, n), r in zip(work, reqs):
            assert list(r["out"]) == oracle(p, n)
        assert sup.restarts == 1 and sup.recovered >= 1
        assert _terminal_counts(reqs) == [1] * 4
    finally:
        fi.clear("serving.decode")
        sup.stop()
        srv.shutdown(timeout=30)
        eng.reset_state()
    _pristine(eng)


# -- (e) window tables and counts that ride on the fetches ----------------------

def test_command_a_plus_tiny_streams_and_counts_with_a_launch_in_flight():
    """Window 8, blocks of 4: a window table frees blocks behind the window
    at every enqueue while the launch before it is in flight. The streams
    are `generate`'s, and the experts' rows summed over the fetches are the
    rows that went through the layers, each launch's once: top-4 of the 16
    held experts for every row of every chunk's bucket and of every decode
    launch's three slots, in 4 layers."""
    from benchmark.lib import weights_cohere2_moe as W
    from benchmark.runners import serve_paged_moe as runner
    cfg = dict(hidden_size=32, head_dim=16, intermediate_size=48,
               num_attention_heads=4, num_key_value_heads=2,
               num_shared_experts=2, num_experts=16, experts_held_from=0,
               num_experts_published=16, num_experts_per_tok=4,
               vocab_size=96, num_hidden_layers=4,
               layer_types=(["sliding_attention"] * 3
                            + ["full_attention"]) * 2,
               layer_switch=4, sliding_window=8, rope_theta=50000,
               layer_norm_eps=1e-5, logit_scale=1, norm_topk_prob=True,
               max_position_embeddings=4096, dtype="float32")
    args = dict(max_slots=3, max_seq=64, block_size=4, prefill_chunk=8)
    eng, ref = (PagedLlamaDecodeEngine(
        runner.build_model(cfg, W.seed_u32(7), "float32"), **args)
        for _ in range(2))
    flight.clear()
    work = [(_prompt(90, 29), 9), (_prompt(91, 5), 14), (_prompt(92, 17), 1),
            (_prompt(93, 33), 12), (_prompt(94, 9), 6)]
    srv = GenerationServer(eng)
    rows = {"n": 0}
    carried = eng._take_aux

    def counted(fetched, unfetched):
        toks, counts = carried(fetched, unfetched)
        rows["n"] += counts["moe_rows"]
        return toks, counts

    eng._take_aux = counted
    try:
        reqs = [srv.submit(p, n) for p, n in work]
        _wait(reqs, 300)
        for (p, n), r in zip(work, reqs):
            assert list(r["out"]) == ref.generate(p, max_new_tokens=n)
        assert srv.shutdown(drain=True, timeout=60)
    finally:
        srv.shutdown(timeout=10)
    layers, top_k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    through = 3 * srv.stats()["steps_run"] + sum(
        e["attrs"]["bucket"] for e in flight.events(category="serving")
        if e["name"] == "prefill_chunk")
    assert rows["n"] == through * layers * top_k
    assert srv.stats()["launched_ahead"] > srv.stats()["steps_run"] // 2
    # what the batch's last fetch brought, under no launch's span, waits
    # for the next span and is no part of any yet
    assert not eng._aux_pending
    assert srv._aux_carry["moe_launches"] == 1
    for c in eng._kv.kinds.values():
        assert c.used_blocks() == 0 and c.stats()["blocks_reserved"] == 0
    assert eng._kv.kinds["window"].num_blocks < eng._kv.kinds["full"].num_blocks


# -- (f) a draft attached -------------------------------------------------------

def test_with_a_draft_the_loop_lands_everything_before_it_speculates(
        model, oracle):
    eng = PagedLlamaDecodeEngine(model, max_slots=2, **GEO)
    eng.attach_draft(eng.make_draft(model, num_layers=1), spec_tokens=3)
    seen = []
    spec_step = eng.spec_step

    def checked():
        # a speculative step reads the host's last_ids and decides pos:
        # nothing may be in flight when it starts
        seen.append((eng._ahead is None, not eng._first_dev))
        return spec_step()

    eng.spec_step = checked
    srv = GenerationServer(eng)
    try:
        work = [(_prompt(95, 6), 18), (_prompt(96, 21), 11),
                (_prompt(97, 3), 25)]
        reqs = [srv.submit(p, n) for p, n in work]
        _wait(reqs)
        for (p, n), r in zip(work, reqs):
            assert list(r["out"]) == oracle(p, n)
        assert seen and all(a and b for a, b in seen)
        assert srv.stats()["launched_ahead"] == 0
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    st = eng._kv.stats()
    assert st["blocks_used"] == 0 and st["blocks_reserved"] == 0


def test_a_slot_taken_again_on_the_pass_that_speculates_again(model, oracle):
    """Plain passes under a draft (a brownout holds speculation off), an
    EOS that frees slot 0 under a launch in flight, the brownout lifted at
    that boundary, and a one-chunk prompt admitted to slot 0 on the very
    next pass, which speculates: the loop lands the old launch, then the
    new prompt's first token, in the order they were enqueued, and the old
    launch's token for slot 0 never reaches the host's `last_ids`, which
    the speculative step reads."""
    budget = 12
    p, eos, k = _eos_case(oracle, "middle", budget)
    clean = (q for q in (_prompt(s, 7) for s in range(400, 500))
             if eos not in oracle(q, 40))
    by, nxt = next(clean), next(clean)
    eng = PagedLlamaDecodeEngine(model, max_slots=2, **GEO)
    eng.attach_draft(eng.make_draft(model, num_layers=1), spec_tokens=3)
    eng.eos_id = eos
    eng._spec_suppressed = True
    srv = GenerationServer(eng)
    first = {}
    passes = {"plain": 0, "spec": 0}
    on_step, spec_step, enqueue = (srv.policy.on_step, eng.spec_step,
                                   eng.step_enqueue)

    def brownout(server):
        # the adaptive policy's seam, at the step boundary: speculation
        # stays off while the first request lives
        on_step(server)
        server._apply_brownout("req" not in first
                               or not first["req"]["done"].is_set(), None)

    def counted_enqueue():
        passes["plain"] += 1
        return enqueue()

    def checked_spec():
        if not passes["spec"]:
            # the first speculative step: slot 0 is the new request's,
            # one token in, and the host holds that token
            second = srv._slots[0]
            assert second is first["nxt"] and len(second["out"]) == 1
            assert eng.last_ids[0, 0] == second["out"][0]
            assert eng._ahead is None and not eng._first_dev
        passes["spec"] += 1
        return spec_step()

    srv.policy.on_step = brownout
    eng.step_enqueue, eng.spec_step = counted_enqueue, checked_spec
    over0 = _M_overrun.value()
    try:
        first["req"] = srv.submit(p, budget)                 # slot 0
        bystander = srv.submit(by, 40)                       # slot 1
        first["nxt"] = srv.submit(nxt, 9)                    # waits for 0
        _wait([first["req"], bystander, first["nxt"]])
        assert list(first["req"]["out"]) == oracle(p, budget, eos)
        assert len(first["req"]["out"]) == k + 1
        assert list(first["nxt"]["out"]) == oracle(nxt, 9)
        assert list(bystander["out"]) == oracle(by, 40)
        assert passes["plain"] >= k and passes["spec"] >= 1
        assert _M_overrun.value() - over0 == 1
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    st = eng._kv.stats()
    assert st["blocks_used"] == 0 and st["blocks_reserved"] == 0
