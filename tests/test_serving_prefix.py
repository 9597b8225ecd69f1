"""Prefix-sharing KV cache (ISSUE 16): radix-tree block reuse with
copy-on-write.

Host-side allocator tests (jax-free: pure PagedKVCache churn) pin the
refcount/partition invariants, the boundary-only COW contract, commit
dedupe, LRU eviction under pressure and the FLAGS_serving_prefix_cache
off-path byte-equivalence. Engine tests pin the end-to-end promise:
aliased prefixes produce BIT-equal greedy streams (the whole point —
sharing must be invisible in the tokens), including over speculative
decode's accept/rollback and across a crash-recovery ``reset_state``.

Oracle strategy mirrors test_serving_paged.py: ``greedy_ref``
(tests/conftest.py: the model's own ``generate``) provides memoized
reference streams; prefix-cache-off engines re-derive the SAME streams
so on/off equality is a three-way pin.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import PagedLlamaDecodeEngine
from paddle_tpu.serving_cache import PagedKVCache
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, use_flash_attention=False)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


def _invariants(kv):
    """Full allocator probe: three-way physical partition, per-row
    table uniqueness, then the allocator's own assertion suite."""
    st = kv.stats()
    owned = sum(len(b) for b in kv._owned.values())
    assert st["blocks_free"] + owned + st["blocks_cached"] \
        == kv.num_blocks
    for row in kv.block_tables:
        row = row[row >= 0]
        assert len(set(row.tolist())) == len(row)
    kv.check_invariants()


# ---------------------------------------------------------------------------
# host allocator: radix tree refcounts, COW contract, eviction
# ---------------------------------------------------------------------------

P16 = list(range(1, 17))     # 4 full blocks at block_size 4
P8 = P16[:8]                 # 2 full blocks


def _kv(num_blocks=16, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 32)
    kw.setdefault("block_size", 4)
    return PagedKVCache(num_blocks=num_blocks, **kw)


class TestRadixAllocator:
    def test_refcount_churn_invariants(self):
        """Interleaved admit/commit/alias/truncate/release churn keeps
        every invariant at every step, and full drain leaves the tree
        cached at ref 0 with zero live/reserved blocks."""
        kv = _kv()
        assert kv.admit(0, 16, 20, token_ids=P16)
        _invariants(kv)
        assert kv.commit_prefix(0, P16, 16) == 4
        _invariants(kv)
        # aliasing admission while the owner is still live
        assert kv.admit(1, 16, 24, token_ids=P16)
        assert kv.matched_tokens(1) == 15            # full match: n-1
        assert kv.take_cow(1) is not None
        _invariants(kv)
        # divergent prompt sharing the first 2 blocks only
        assert kv.admit(2, 16, 16, token_ids=P8 + [90, 91, 92, 93,
                                                   94, 95, 96, 97])
        assert kv.matched_tokens(2) == 8
        assert kv.take_cow(2) is None                # not block-aligned
        _invariants(kv)
        kv.ensure_token(0, 16)                       # draw reservation
        _invariants(kv)
        kv.truncate(1, 8)                            # back into prefix
        _invariants(kv)
        for s in (0, 2, 1):
            kv.release(s)
            _invariants(kv)
        st = kv.stats()
        assert st["blocks_used"] == 0
        assert st["blocks_reserved"] == 0
        assert st["blocks_cached"] == st["blocks_evictable"] > 0
        assert st["prefix_hits"] == 2
        assert st["prefix_tokens_reused"] == 15 + 8

    def test_full_match_cow_accounting(self):
        """A block-aligned full-prompt match aliases all but the
        boundary block, which is cloned (one extra charged block) so
        the re-prefilled last token writes privately; the clone is
        handed out exactly once via take_cow."""
        kv = _kv(num_blocks=6)
        assert kv.admit(0, 8, 8, token_ids=P8)
        kv.commit_prefix(0, P8, 8)
        kv.release(0)
        free_before = kv.stats()["blocks_free"]
        assert kv.admit(1, 8, 8, token_ids=P8)
        assert kv.matched_tokens(1) == 7
        mv = kv.take_cow(1)
        assert mv is not None
        src, dst = mv
        assert kv._by_block[src].ref == 0            # boundary decref'd
        assert dst in kv._owned[1]
        assert kv.take_cow(1) is None                # consumed
        assert len(kv._shared[1]) == 1               # only block 0 aliased
        assert kv.stats()["blocks_free"] == free_before - 1
        _invariants(kv)
        kv.release(1)
        _invariants(kv)

    def test_boundary_only_cow_and_mid_prefix_raises(self):
        """cow_for_write detaches ONLY the last shared block; a write
        addressed inside the prefix is a corruption bug and raises."""
        kv = _kv()
        assert kv.admit(0, 16, 16, token_ids=P16)
        kv.commit_prefix(0, P16, 16)
        kv.release(0)
        assert kv.admit(1, 16, 16, token_ids=P16)
        kv.take_cow(1)                               # 3 aliased remain
        with pytest.raises(RuntimeError, match="INSIDE"):
            kv.cow_for_write(1, 0)
        src, dst = kv.cow_for_write(1, 11)           # boundary block 2
        assert kv.block_tables[1, 2] == dst != src
        assert kv.cow_for_write(1, 11) is None       # now private
        _invariants(kv)
        kv.release(1)

    def test_commit_dedupe_remaps_to_cached_block(self):
        """Two writers prefilling the same prompt concurrently (the
        second admitted BEFORE the first committed, so no match):
        the later commit dedupes against the tree, frees its private
        duplicate and aliases the cached block."""
        kv = _kv()
        assert kv.admit(0, 8, 8, token_ids=P8)
        assert kv.admit(1, 8, 8, token_ids=P8)       # nothing cached yet
        assert kv.matched_tokens(1) == 0
        kv.commit_prefix(0, P8, 8)
        free_before = kv.stats()["blocks_free"]
        assert kv.commit_prefix(1, P8, 8) == 2
        # both private blocks returned; slot 1 now aliases slot 0's
        assert kv.stats()["blocks_free"] == free_before + 2
        assert kv._owned[1] == []
        assert list(kv.block_tables[1, :2]) == \
            list(kv.block_tables[0, :2])
        for b in kv._shared[1]:
            assert kv._by_block[b].ref == 2
        _invariants(kv)
        kv.release(0)
        kv.release(1)
        _invariants(kv)

    def test_eviction_under_pressure_recovers_admissions(self):
        """Cached (ref-0) prefix blocks are reclaimable supply: an
        admission that outgrows the free list LRU-evicts leaves
        instead of deferring, and the eviction counter moves."""
        kv = _kv(num_blocks=4)
        assert kv.admit(0, 16, 16, token_ids=P16)
        kv.commit_prefix(0, P16, 16)
        kv.release(0)
        st = kv.stats()
        assert st["blocks_free"] == 0
        assert st["blocks_evictable"] == 4
        assert st["blocks_available"] == 4
        # a DIFFERENT prompt: no match, needs 2 real blocks
        assert kv.admit(1, 8, 8, token_ids=[70 + i for i in range(8)])
        assert kv.evictions == 2
        # deepest (leaf) nodes went first; the root-side survive
        assert kv.stats()["blocks_cached"] == 2
        _invariants(kv)
        # and the survivors still match a shorter shared prefix
        kv.release(1)
        assert kv.admit(2, 8, 8, token_ids=P8)
        assert kv.matched_tokens(2) == 7             # full 2-block match
        kv.release(2)
        _invariants(kv)

    def test_matched_path_never_self_evicts(self):
        """Admission increfs its matched path BEFORE allocating, so
        the eviction pass can never reclaim the very blocks the
        admission is aliasing."""
        kv = _kv(num_blocks=5)
        assert kv.admit(0, 16, 16, token_ids=P16)
        kv.commit_prefix(0, P16, 16)
        kv.release(0)
        # full match + COW clone: the pop must evict a TREE leaf (the
        # boundary src it just decref'd is the LRU-newest, so the old
        # spare free block covers it), never blocks 0-2 of the path
        assert kv.admit(1, 16, 16, token_ids=P16)
        path_blocks = list(kv._shared[1])
        assert all(b in kv._by_block for b in path_blocks)
        _invariants(kv)
        kv.release(1)

    def test_prefix_cap_bounds_tree(self):
        """FLAGS_serving_prefix_cache_blocks caps resident tree
        blocks; past the cap, commits evict ref-0 nodes or leave the
        suffix private."""
        kv = _kv(num_blocks=16, prefix_cache_blocks=2)
        assert kv.admit(0, 16, 16, token_ids=P16)
        kv.commit_prefix(0, P16, 16)
        assert kv.stats()["blocks_cached"] == 2      # capped
        _invariants(kv)
        kv.release(0)
        _invariants(kv)

    def test_reset_prefix_cache_requires_drained_slots(self):
        kv = _kv()
        assert kv.admit(0, 8, 8, token_ids=P8)
        kv.commit_prefix(0, P8, 8)
        with pytest.raises(RuntimeError, match="live shared"):
            kv.reset_prefix_cache()
        kv.release(0)
        assert kv.reset_prefix_cache() == 2
        st = kv.stats()
        assert st["blocks_cached"] == 0
        assert st["blocks_free"] == kv.num_blocks
        _invariants(kv)


# ---------------------------------------------------------------------------
# FLAGS_serving_prefix_cache=0: the off path is the old allocator
# ---------------------------------------------------------------------------

class TestPrefixCacheFlagOff:
    def _script(self, kv):
        """A representative allocator scenario (the
        test_serving_paged.py churn slice) returning every observable
        the old design exposed."""
        trace = []
        assert kv.admit(0, 8, 16, token_ids=P8)
        kv.commit_prefix(0, P8, 8)
        assert kv.admit(1, 8, 16, token_ids=P8)      # would match if on
        trace.append(kv.matched_tokens(1))
        kv.ensure_token(0, 8)
        kv.truncate(0, 6)
        kv.release(0)
        assert kv.admit(2, 4, 12, token_ids=P8[:4])
        trace.append((kv.block_tables.copy().tobytes(),
                      tuple(sorted(kv._free)), kv.stats()))
        kv.release(1)
        kv.release(2)
        trace.append(kv.stats())
        return trace

    def test_flag_off_is_byte_identical_to_plain_allocator(self):
        """With the flag off the allocator must behave byte-for-byte
        like one with no prefix machinery at all: same block tables,
        same free list, same stats, zero cache/hit activity — pinned
        by running the same scripted scenario through the flag path
        and the explicit prefix_cache=False constructor."""
        prev = paddle.get_flags(["FLAGS_serving_prefix_cache"])
        paddle.set_flags({"FLAGS_serving_prefix_cache": 0})
        try:
            via_flag = self._script(_kv(num_blocks=8))
        finally:
            paddle.set_flags(prev)
        via_arg = self._script(_kv(num_blocks=8, prefix_cache=False))
        assert via_flag == via_arg
        # no match was served, nothing was cached
        assert via_flag[0] == 0
        final = via_flag[-1]
        assert final["blocks_cached"] == 0
        assert final["blocks_evictable"] == 0
        assert final["prefix_hits"] == 0
        assert final["prefix_tokens_reused"] == 0
        assert final["blocks_used"] == 0
        assert final["blocks_free"] == 8
        # off path: available degenerates to the pre-sharing formula
        st = via_flag[1][2]
        assert st["blocks_available"] == \
            st["blocks_free"] - st["blocks_reserved"]

    @pytest.mark.slow  # ~6s: compiles two engines (flag on AND off)
    def test_flag_off_streams_match_flag_on(self, model, greedy_ref):
        """Engine-level pin BOTH ways: repeated shared-prefix prompts
        produce identical greedy streams with the prefix cache on and
        off, and both equal the model's own stream."""
        prev = paddle.get_flags(["FLAGS_serving_prefix_cache"])
        paddle.set_flags({"FLAGS_serving_prefix_cache": 0})
        try:
            off = PagedLlamaDecodeEngine(model, max_slots=2,
                                         max_seq=64, block_size=8,
                                         prefill_chunk=8)
            assert not off._kv.prefix_enabled
            prompts = [list(range(3, 19)), list(range(3, 19)),
                       list(range(3, 19)) + [40, 41]]
            got_off = [off.generate(p, max_new_tokens=8)
                       for p in prompts]
        finally:
            paddle.set_flags(prev)
        assert off._kv.stats()["prefix_hits"] == 0
        on = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                    block_size=8, prefill_chunk=8)
        got_on = [on.generate(p, max_new_tokens=8) for p in prompts]
        assert on._kv.stats()["prefix_hits"] >= 1
        for p, a, b in zip(prompts, got_off, got_on):
            want = greedy_ref(p, 8)
            assert a == want and b == want, (p, a, b, want)


# ---------------------------------------------------------------------------
# engine: shared prefixes are invisible in the tokens
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prefix_eng(model):
    """Shared prefix-cache-on engine: 2 slots over 64 tokens, 8-token
    blocks/chunks (so a 16-token prompt is exactly 2 radix nodes)."""
    return PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                  block_size=8, prefill_chunk=8)


class TestPrefixEngineBitEquality:
    def test_cow_boundary_bit_equal_vs_the_models_stream(
            self, model, greedy_ref, prefix_eng):
        """Cold miss, full block-aligned hit (COW boundary clone) and
        partial hit all reproduce the model's stream exactly, while the
        hit/reuse counters prove sharing actually happened."""
        from paddle_tpu.observability import flight

        eng = prefix_eng
        P = list(range(3, 19))                       # 2 full blocks
        st0 = eng._kv.stats()
        cold = eng.generate(P, max_new_tokens=10)
        assert cold == greedy_ref(P, 10)
        assert eng._kv.stats()["prefix_hits"] == st0["prefix_hits"]
        # full hit: n-1 tokens skip prefill, boundary block COW-cloned
        hot = eng.generate(P, max_new_tokens=10)
        assert hot == cold
        st1 = eng._kv.stats()
        assert st1["prefix_hits"] == st0["prefix_hits"] + 1
        assert st1["prefix_tokens_reused"] >= \
            st0["prefix_tokens_reused"] + 15
        names = [e["name"] for e in flight.events(category="serving")]
        assert "prefix_hit" in names and "prefix_cow" in names
        # partial hit: shared head, divergent tail
        Q = P[:8] + [50, 51, 52, 53]
        assert eng.generate(Q, max_new_tokens=10) == greedy_ref(Q, 10)
        assert eng._kv.stats()["prefix_hits"] == st1["prefix_hits"] + 1
        _invariants(eng._kv)
        assert eng._kv.stats()["blocks_used"] == 0

    def test_interleaved_sharers_and_metrics(self, model, greedy_ref,
                                             prefix_eng):
        """Two LIVE slots aliasing one cached prefix decode
        interleaved without cross-talk, and the per-request
        prefix_hit_tokens record survives until release."""
        eng = prefix_eng
        P = list(range(3, 19))
        greedy_ref(P, 6)                              # warm the oracle
        eng.generate(P, max_new_tokens=4)            # seed the tree
        o0 = [eng.prefill(0, P, budget=8)]
        o1 = [eng.prefill(1, P, budget=8)]
        assert eng.prefix_hit_tokens[0] == 15
        assert eng.prefix_hit_tokens[1] == 15
        _invariants(eng._kv)
        for _ in range(5):
            nxt = eng.step()
            o0.append(int(nxt[0]))
            o1.append(int(nxt[1]))
        eng.release(0)
        eng.release(1)
        assert 0 not in eng.prefix_hit_tokens
        want = greedy_ref(P, 6)
        assert o0 == want and o1 == want
        _invariants(eng._kv)

    @pytest.mark.slow  # ~5s: compiles a fresh engine + draft spec tree
    def test_spec_rollback_over_shared_prefix(self, model, greedy_ref):
        """Speculative decode over an aliased prefix: the draft pool
        mirrors the admission (its own radix tree), windows
        accept/roll back across the shared boundary, and the
        committed stream still matches the model's bit-for-bit
        with both pools' invariants intact after every window."""
        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                     block_size=8, prefill_chunk=8)
        eng.attach_draft(eng.make_draft(model, num_layers=1),
                         spec_tokens=3)
        P = list(range(3, 19))
        want = greedy_ref(P, 12)
        assert eng.generate(P, max_new_tokens=12) == want  # cold
        out = [eng.prefill(0, P, budget=16)]         # hot: prefix hit
        assert eng.prefix_hit_tokens[0] == 15
        assert eng._draft.prefix_hit_tokens[0] == 15
        while len(out) < 12:
            toks, counts = eng.spec_step()
            out.extend(int(t) for t in toks[0, :int(counts[0])])
            _invariants(eng._kv)
            _invariants(eng._draft._kv)
        eng.release(0)
        assert out[:12] == want, (out, want)
        assert eng._kv.stats()["blocks_used"] == 0
        assert eng._draft._kv.stats()["blocks_used"] == 0
        _invariants(eng._kv)
        _invariants(eng._draft._kv)

    def test_reset_state_chaos_mid_prefill(self, model, greedy_ref,
                                           prefix_eng):
        """Crash recovery with a warm tree, a live sharer AND a
        mid-prefill staged request: reset_state drops the radix cache
        with the pools (cached content is no longer backed by real
        K/V), and post-reset streams rebuild it from zero, bit-equal.
        This is the supervisor's _handle_death seam — it calls
        exactly this method on the quarantined engine."""
        eng = prefix_eng
        P = list(range(3, 19))
        eng.generate(P, max_new_tokens=4)            # warm tree
        assert eng._kv.stats()["blocks_cached"] > 0
        assert eng.begin_request(0, P, 8)            # live sharer
        assert eng.begin_request(1, list(range(30, 46)), 8)
        eng.prefill_chunk(1)                         # mid-prefill
        eng.reset_state()
        st = eng._kv.stats()
        assert st["blocks_used"] == 0
        assert st["blocks_cached"] == 0
        assert st["blocks_reserved"] == 0
        assert st["blocks_free"] == eng._kv.num_blocks
        assert eng.prefix_hit_tokens == {}
        assert not eng._prefill_state
        _invariants(eng._kv)
        # the tree is gone: the next request is a cold miss that
        # re-seeds it, and the stream is still exact
        st0 = eng._kv.stats()["prefix_hits"]
        assert eng.generate(P, max_new_tokens=6) == greedy_ref(P, 6)
        assert eng._kv.stats()["prefix_hits"] == st0
        assert eng.generate(P, max_new_tokens=6) == greedy_ref(P, 6)
        assert eng._kv.stats()["prefix_hits"] == st0 + 1
        _invariants(eng._kv)
