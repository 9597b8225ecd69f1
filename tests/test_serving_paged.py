"""Paged KV cache serving (ISSUE 11): block-pool allocator invariants,
decode equivalence with the model's own greedy stream across bucketed
prompt lengths, chunked prefill/decode interleave under
GenerationServer, exhaustion and eviction accounting, and the captured
decode step's 0-host-sync steady state.

Oracle strategy: ``greedy_ref`` (tests/conftest.py) is
``LlamaForCausalLM.generate``, a forward pass independent of the engine;
the engine must reproduce its greedy streams exactly. Streams are
memoized by prompt for the module.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine
from paddle_tpu.serving_cache import PagedKVCache

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, use_flash_attention=False)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


@pytest.fixture(scope="module")
def paged64(model):
    """Shared paged engine (2 slots, max_seq 64, 8-token blocks and
    prefill chunks); tests release every slot they touch."""
    return PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                  block_size=8, prefill_chunk=8)


@pytest.fixture(params=["block8_chunk8", "defaults"])
def eng64(request, model):
    """``paged64``, or the engine at its default block and chunk sizes
    (16 and 64: the geometry test_serving_generation.py serves at),
    both 2 slots of 64 positions."""
    if request.param == "block8_chunk8":
        return request.getfixturevalue("paged64")
    return PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64)


def _wait_steps(srv, n, tries=400):
    for _ in range(tries):
        if srv.steps_run >= n:
            return True
        time.sleep(0.02)
    return False


class TestPagedVsModel:
    def test_bit_equivalence_across_bucketed_prompt_lengths(
            self, model, greedy_ref, paged64):
        """Paged greedy streams match the model's own token-for-token
        for prompts spanning the prefill buckets (3 -> one sub-chunk
        bucket, 30 -> four 8-token chunks crossing block boundaries)."""
        for prompt in ([5, 9, 11, 3], [2], [1, 2, 3, 4, 5, 6, 7, 8],
                       list(range(1, 14)), list(range(3, 33))):
            want = greedy_ref(prompt, 12)
            got = paged64.generate(prompt, max_new_tokens=12)
            assert got == want, (len(prompt), got, want)
        # every request released its blocks + reservation
        st = paged64._kv.stats()
        assert st["blocks_used"] == 0 and st["blocks_reserved"] == 0

    def test_slots_are_independent(self, greedy_ref, eng64):
        """Interleaved slots over a SHARED block pool produce exactly
        their single-request sequences (no cross-slot block leaks)."""
        p0, p1 = [1, 2, 3], [40, 41, 42, 43, 44]
        o0 = [eng64.prefill(0, p0, budget=8)]
        o1 = [eng64.prefill(1, p1, budget=8)]
        for _ in range(5):
            nxt = eng64.step()
            o0.append(int(nxt[0]))
            o1.append(int(nxt[1]))
        eng64.release(0)
        eng64.release(1)
        assert o0 == greedy_ref(p0, 6)
        assert o1 == greedy_ref(p1, 6)

    def test_decode_window_matches_the_model(self, greedy_ref, paged64):
        """decode_steps (device-resident token feedback, one fetch per
        window) over the block pool continues each slot's reference
        stream, with the window's blocks pre-mapped so the device
        table stays valid."""
        p0, p1 = [1, 2, 3], [4, 5]
        paged64.prefill(0, p0, budget=20)
        paged64.prefill(1, p1, budget=20)
        toks = paged64.decode_steps(6)
        paged64.release(0)
        paged64.release(1)
        assert list(toks[0]) == greedy_ref(p0, 7)[1:]
        assert list(toks[1]) == greedy_ref(p1, 7)[1:]

    def test_slot_reuse_after_release(self, eng64):
        a = eng64.generate([7, 8], max_new_tokens=4)
        b = eng64.generate([7, 8], max_new_tokens=4)
        assert a == b  # recycled blocks must not leak stale K/V

    def test_recycled_block_garbage_is_inert(self, greedy_ref, paged64):
        """Blocks recycled from a pathological request (activations
        driven to NaN/inf write non-finite K/V) must be invisible to
        the next request sharing the pool: masked columns contribute
        exactly zero. Pins the 0*NaN=NaN leak in the PV contraction —
        the pool poisons NOTHING even when every stale cell is NaN."""
        import jax.numpy as jnp

        paged64.kvs["k"] = [jnp.full_like(a, jnp.nan)
                            for a in paged64.kvs["k"]]
        paged64.kvs["v"] = [jnp.full_like(a, jnp.nan)
                            for a in paged64.kvs["v"]]
        prompt = [5, 9, 11, 3]
        assert paged64.generate(prompt, max_new_tokens=12) == \
            greedy_ref(prompt, 12)

    def test_quantized_kv_blocks(self, model, greedy_ref):
        """bf16 pools on an f32 model and int8 absmax pools both
        decode deterministically; int8 stays close to the exact
        stream early on (same-first-token sanity)."""
        want = greedy_ref([5, 9, 11], 6)
        for quant in ("bfloat16", "int8"):
            eng = PagedLlamaDecodeEngine(model, max_slots=1, max_seq=64,
                                         block_size=16, kv_quant=quant)
            out = eng.generate([5, 9, 11], max_new_tokens=6)
            assert len(out) == 6
            assert all(0 <= t < CFG["vocab_size"] for t in out)
            assert out == eng.generate([5, 9, 11], max_new_tokens=6)
            assert out[0] == want[0], (quant, out, want)

    @pytest.mark.parametrize("block_size", [8, None],
                             ids=["block8", "default_block"])
    def test_export_decode_roundtrip(self, model, block_size):
        """The decode step AOT-exports with its block-pool signature:
        the serialized step runs without the engine class and matches
        the live step (ref: the predictor's self-contained analyzed
        program)."""
        import jax
        import jax.numpy as jnp

        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32,
                                     block_size=block_size)
        eng.prefill(0, [3, 4, 5], budget=8)
        blob = eng.export_decode()
        assert isinstance(blob, (bytes, bytearray)) and len(blob) > 0
        rebuilt = jax.export.deserialize(bytearray(blob))
        args = (eng.params, eng.kvs, jnp.asarray(eng.last_ids),
                jnp.asarray(eng.pos),
                jnp.asarray(eng._kv.block_tables),
                jnp.asarray(eng.active))
        nxt_aot, _, _ = rebuilt.call(*args)
        nxt_live, _, _ = jax.jit(eng._decode_impl)(*args)
        assert int(nxt_aot[0]) == int(nxt_live[0])

    def test_no_dense_view_in_paged_attention(self, model):
        """Acceptance: the paged decode step never materializes a
        dense [., max_seq] score or cache view — no intermediate in
        its jaxpr (loop bodies included) carries a max_seq-sized
        dimension. max_seq=48 is chosen to collide with no other
        dimension of this geometry."""
        import jax
        import jax.extend.core as jcore
        import jax.numpy as jnp

        max_seq = 48
        eng = PagedLlamaDecodeEngine(model, max_slots=3,
                                     max_seq=max_seq, block_size=16)
        args = (eng.params, eng.kvs, jnp.asarray(eng.last_ids),
                jnp.asarray(eng.pos),
                jnp.asarray(eng._kv.block_tables),
                jnp.asarray(eng.active))
        jaxpr = jax.make_jaxpr(eng._decode_impl)(*args)

        offenders = []

        def walk(jx):
            for eqn in jx.eqns:
                for v in eqn.outvars:
                    shape = getattr(v.aval, "shape", ())
                    if max_seq in tuple(shape):
                        offenders.append((eqn.primitive.name,
                                          tuple(shape)))
                for p in eqn.params.values():
                    for sub in (p if isinstance(p, (list, tuple))
                                else [p]):
                        if isinstance(sub, jcore.Jaxpr):
                            walk(sub)
                        elif isinstance(sub, jcore.ClosedJaxpr):
                            walk(sub.jaxpr)

        walk(jaxpr.jaxpr)
        assert offenders == [], offenders


class TestBlockAllocator:
    def test_admit_extend_release_churn_no_leaks(self):
        """Randomized admit/extend/release churn: blocks are never
        double-owned, free + owned == pool, reservations balance, and
        a full drain returns the pool to its initial state."""
        rng = np.random.default_rng(0)
        kv = PagedKVCache(max_slots=8, max_seq=64, block_size=8,
                          num_blocks=20)
        held = {}  # slot -> next unmapped position
        for _ in range(300):
            op = rng.integers(0, 3)
            if op == 0:  # admit
                free = [s for s in range(8) if s not in held]
                if free:
                    s = int(rng.choice(free))
                    tokens = int(rng.integers(1, 40))
                    if kv.admit(s, min(tokens, 8), tokens):
                        held[s] = min(tokens, 8)
            elif op == 1 and held:  # extend within reservation
                s = int(rng.choice(list(held)))
                pos = held[s]
                bidx = pos // kv.block_size
                if bidx < kv.max_blocks_per_slot and \
                        kv.block_tables[s, bidx] < 0:
                    try:
                        kv.ensure_token(s, pos)
                        held[s] = pos + kv.block_size
                    except RuntimeError:
                        pass  # budget spent: legal terminal state
                else:
                    held[s] = pos + 1
            elif held:  # release
                s = int(rng.choice(list(held)))
                kv.release(s, evicted=bool(rng.integers(0, 2)))
                del held[s]
            st = kv.stats()
            owned = sum(len(b) for b in kv._owned.values())
            assert st["blocks_free"] + owned == 20
            assert st["blocks_reserved"] == sum(kv._reserved.values())
            assert st["blocks_free"] >= st["blocks_reserved"]
            mapped = int((kv.block_tables >= 0).sum())
            assert mapped == owned
            phys = kv.block_tables[kv.block_tables >= 0]
            assert len(set(phys.tolist())) == len(phys)  # no aliasing
        for s in list(held):
            kv.release(s)
        st = kv.stats()
        assert st["blocks_free"] == 20 and st["blocks_used"] == 0
        assert st["blocks_reserved"] == 0
        assert (kv.block_tables == -1).all()

    def test_exhaustion_defers_and_recovers(self):
        kv = PagedKVCache(max_slots=4, max_seq=64, block_size=8,
                          num_blocks=4)
        assert kv.admit(0, 8, 16)          # 2 now, 0 reserved... 2 total
        assert kv.admit(1, 8, 16)
        assert not kv.admit(2, 8, 16)      # pool covered: defer
        assert kv.stats()["blocks_available"] == 0
        kv.release(0)
        assert kv.admit(2, 8, 16)          # recovered

    def test_impossible_request_raises(self):
        kv = PagedKVCache(max_slots=2, max_seq=256, block_size=8,
                          num_blocks=4)
        with pytest.raises(ValueError, match="pool holds only"):
            kv.admit(0, 8, 200)            # needs 25 blocks of 4

    def test_reservation_guarantees_extension(self):
        """The admission invariant: a second admit cannot eat blocks
        an earlier request reserved for its decode tail."""
        kv = PagedKVCache(max_slots=2, max_seq=64, block_size=8,
                          num_blocks=3)
        assert kv.admit(0, 4, 24)          # 1 mapped + 2 reserved
        assert not kv.admit(1, 4, 8)       # nothing left to reserve
        kv.ensure_token(0, 8)
        kv.ensure_token(0, 16)             # reservation fully drawn
        assert kv.stats()["blocks_used"] == 3

    def test_eviction_counter_counts_reclaims_only(self):
        kv = PagedKVCache(max_slots=2, max_seq=32, block_size=8,
                          num_blocks=4)
        kv.admit(0, 8, 8)
        kv.release(0)                      # normal completion
        assert kv.evictions == 0
        kv.admit(1, 16, 16)
        kv.release(1, evicted=True)        # deadline/failure reclaim
        assert kv.evictions == 2


class TestServerInterleave:
    def test_concurrent_requests_share_pool(self, model, greedy_ref):
        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                     block_size=8, prefill_chunk=8)
        srv = GenerationServer(eng)
        jobs = [([1, 2, 3], 8), ([40, 41], 5), (list(range(1, 25)), 6)]
        results = {}

        def run(i, prompt, n):
            results[i] = srv.generate(prompt, n, timeout=120)

        ts = [threading.Thread(target=run, args=(i, p, n))
              for i, (p, n) in enumerate(jobs)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        for i, (p, n) in enumerate(jobs):
            assert results[i] == greedy_ref(p, n), i
        assert srv.admitted == 3
        assert srv.shutdown(drain=True, timeout=120)
        assert srv.stats()["kv_pool"]["blocks_used"] == 0

    def test_pool_exhaustion_queues_not_crashes(self, model, greedy_ref):
        """More requests than the pool covers: the overflow WAITS for
        blocks (never a loop crash), is admitted as earlier requests
        release, and every stream still matches its oracle."""
        eng = PagedLlamaDecodeEngine(model, max_slots=4, max_seq=64,
                                     block_size=8, num_blocks=4,
                                     prefill_chunk=8)
        srv = GenerationServer(eng)
        reqs = [srv.submit([1, 2, 3, 4, 5, 6, 7], 8) for _ in range(5)]
        for r in reqs:
            assert r["done"].wait(120), srv.stats()
            assert r["error"] is None, r["error"]
            assert list(r["out"]) == greedy_ref([1, 2, 3, 4, 5, 6, 7], 8)
        st = srv.stats()
        assert st["kv_pool"]["blocks_used"] == 0
        assert srv.shutdown(drain=True, timeout=60)

    def test_deferred_request_is_not_starved(self, model, greedy_ref):
        """Head-of-line fairness: while a large request waits for
        blocks, newer small requests must NOT be admitted past it and
        re-consume every freed block — the deferred request admits
        first once capacity frees."""
        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                     block_size=8, num_blocks=4,
                                     prefill_chunk=8)
        orig_step = eng.step_collect

        def slow_step(launch):
            time.sleep(0.03)
            return orig_step(launch)

        eng.step_collect = slow_step
        srv = GenerationServer(eng)
        small_a = srv.submit([1, 2, 3], 12)       # 2 blocks, runs long
        assert _wait_steps(srv, 2)
        big = srv.submit(list(range(1, 17)), 15)  # needs all 4 blocks
        small_c = srv.submit([4, 5], 6)           # 1 block, arrives last
        for r in (small_a, big, small_c):
            assert r["done"].wait(120) and r["error"] is None, r["error"]
        # the big request was admitted BEFORE the later small one
        assert big["t_admit"] < small_c["t_admit"], (
            big["t_admit"], small_c["t_admit"])
        assert list(big["out"]) == greedy_ref(list(range(1, 17)), 15)
        srv.shutdown()

    def test_drain_shutdown_with_prefill_in_flight(self, model,
                                                   greedy_ref):
        """Drain during a chunked prefill: the half-prefilled long
        prompt AND everything queued complete with full oracle
        streams before the loop exits."""
        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=256,
                                     block_size=16, prefill_chunk=8)
        srv = GenerationServer(eng)
        short = srv.submit([1, 2, 3], 10)
        assert _wait_steps(srv, 2)
        long_p = list(range(2, 60))        # 58 tokens -> 8 chunks
        long = srv.submit(long_p, 6)
        queued = srv.submit([7, 9, 2], 5)
        assert srv.shutdown(drain=True, timeout=180)
        for req, (p, n) in ((short, ([1, 2, 3], 10)),
                            (long, (long_p, 6)),
                            (queued, ([7, 9, 2], 5))):
            assert req["done"].is_set()
            assert req["error"] is None, req["error"]
            assert list(req["out"]) == greedy_ref(p, n)
        assert srv.stats()["kv_pool"]["blocks_used"] == 0

    def test_expired_requests_return_blocks_as_evictions(self, model,
                                                         greedy_ref):
        """Deadline expiry — waiting-for-blocks OR active — frees the
        blocks and counts them into block_evictions_total."""
        eng = PagedLlamaDecodeEngine(model, max_slots=1, max_seq=64,
                                     block_size=8, num_blocks=4,
                                     prefill_chunk=8)
        orig_step = eng.step_collect

        def slow_step(launch):
            time.sleep(0.05)
            return orig_step(launch)

        eng.step_collect = slow_step
        srv = GenerationServer(eng)
        # compile prefill (bucket 8) + decode BEFORE any deadline clock
        # starts: the deadlines below race 50 ms steps, not XLA
        srv.generate([1, 2, 3], 2, timeout=120)
        blocker = srv.submit([1, 2, 3], 25)        # hogs slot + blocks
        starved = srv.submit([9, 8], 8, deadline=0.3)
        assert starved["done"].wait(60)
        assert isinstance(starved["error"], TimeoutError)
        assert blocker["done"].wait(120) and blocker["error"] is None
        # admitted at once (the slot is free); 24 steps x 50 ms cannot
        # finish inside the deadline, so it expires while ACTIVE
        active = srv.submit(list(range(1, 6)), 24, deadline=0.6)
        assert active["done"].wait(120)
        assert isinstance(active["error"], TimeoutError)
        assert len(active["out"]) >= 1             # partials retained
        assert eng._kv.evictions >= 1              # reclaim counted
        assert eng._kv.stats()["blocks_used"] == 0
        # pool recovered: a fresh request still serves
        assert srv.generate([1, 2, 3], 2, timeout=60) == \
            greedy_ref([1, 2, 3], 2)
        srv.shutdown()

    @pytest.mark.slow
    def test_long_prompt_does_not_stall_decode(self, model):
        """Acceptance regression: per-step decode latency for an
        already-admitted stream while a long prompt chunk-prefills
        stays within 2x its no-prefill baseline (+ scheduling slack).
        Gaps come from the flight recorder's per-step decode events,
        so the measurement sees exactly what the loop does."""
        from paddle_tpu.observability import flight

        def median_decode_gap(with_long_prompt):
            eng = PagedLlamaDecodeEngine(model, max_slots=2,
                                         max_seq=512, block_size=16,
                                         prefill_chunk=16)
            srv = GenerationServer(eng)
            a = srv.submit([1, 2, 3], 60)
            assert _wait_steps(srv, 4)
            if with_long_prompt:
                srv.submit(list(range(2, 300)), 4)   # ~19 chunks
            assert a["done"].wait(180)
            assert srv.shutdown(drain=True, timeout=180)
            ev = [e for e in flight.events(trace_id=a["trace_id"])
                  if e["name"] == "decode"]
            gaps = np.diff([e["ts_us"] for e in ev]) / 1e6
            assert len(gaps) >= 20
            return float(np.median(gaps))

        base = median_decode_gap(False)
        overlapped = median_decode_gap(True)
        assert overlapped <= 2.0 * base + 0.05, (overlapped, base)


class TestPagedCapture:
    def test_paged_decode_step_audits_zero_syncs(self, model):
        """The captured paged decode step runs 0 host syncs in steady
        state and counts into sot.captured_steps_total (capture_jit
        accounting)."""
        import jax.numpy as jnp
        from paddle_tpu import analysis
        from paddle_tpu.observability import metrics as om

        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                     block_size=8)
        eng.prefill(0, [1, 2, 3], budget=30)
        eng.prefill(1, [4, 5], budget=30)
        for _ in range(3):                 # warm + steady state
            eng.step()

        def one_captured_step():
            eng._extend_tables()
            nxt, eng.kvs, _ = eng._decode(
                eng.params, eng.kvs, jnp.asarray(eng.last_ids),
                jnp.asarray(eng.pos), jnp.asarray(eng._kv.block_tables),
                jnp.asarray(eng.active))
            return nxt

        before = dict(om.snapshot().get("sot", {}))
        rep = analysis.audit(one_captured_step)
        after = dict(om.snapshot().get("sot", {}))
        assert rep.syncs == [], rep.syncs
        assert not [d for d in rep.diagnostics
                    if d.rule in ("PTA001", "PTA002", "PTA003")], \
            [d.to_dict() for d in rep.diagnostics]
        got = after.get("captured_steps_total", 0) - \
            before.get("captured_steps_total", 0)
        assert got >= 1, (before, after)

    def test_block_pool_gauges_and_flight_events(self, model):
        """serving.blocks_free/blocks_used track the pool and the
        flight journal carries block_alloc/block_free (and
        block_exhausted on a deferred admission)."""
        from paddle_tpu.observability import flight
        from paddle_tpu.observability import metrics as om

        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                     block_size=8, num_blocks=4)
        assert eng.begin_request(0, [1, 2, 3, 4, 5, 6, 7, 8, 9], 14)
        snap = om.snapshot()["serving"]
        assert snap["blocks_used"] == 2          # 9 tokens -> 2 blocks
        assert snap["blocks_free"] == 4 - 3      # +1 block reserved
        assert not eng.begin_request(1, [1] * 9, 14)  # exhausted
        eng.release(0, evicted=True)
        snap = om.snapshot()["serving"]
        assert snap["blocks_used"] == 0 and snap["blocks_free"] == 4
        names = [e["name"] for e in flight.events(category="serving")]
        for expected in ("block_alloc", "block_exhausted",
                         "block_free"):
            assert expected in names, names
