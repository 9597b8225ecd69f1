"""Speculative decoding on the paged KV pool + the Pallas block-table
paged-attention kernel seam (ISSUE 12).

Oracle strategy, in two layers:

- TOKENS: the non-speculative paged engine (itself pinned against
  LlamaForCausalLM.generate in test_serving_paged.py) is the stream
  reference — greedy speculative decode must reproduce it
  BIT-exactly, because every committed token conditions on a committed
  prefix (the accept rule). A 1-of-2-layer random draft disagrees with
  its target constantly, so these streams exercise rejection mid-window
  and rollback on nearly every step.
- NUMERICS: the pure-jnp tile walk in ``serving_cache.paged_attention``
  is the kernel's oracle — the Pallas kernel runs through the
  interpreter on CPU (skipped, not failed, where Pallas is missing) and
  must agree on every geometry.
"""
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
def paged_ref(model):
    """Non-speculative paged reference engine + memoized greedy
    streams (max_seq 256 so no reference stream truncates early)."""
    eng = PagedLlamaDecodeEngine(model, max_slots=1, max_seq=256,
                                 block_size=8, prefill_chunk=8)
    cache = {}

    def ref(prompt, n_new):
        key = (tuple(int(t) for t in prompt), int(n_new))
        if key not in cache:
            cache[key] = eng.generate(list(key[0]), max_new_tokens=n_new)
        return cache[key]

    return ref


@pytest.fixture(scope="module")
def spec_eng(model):
    """Shared speculative engine: 2 slots over a 64-token paged space,
    8-token blocks/chunks, a truncated-layer draft (1 of 2 layers,
    weight-shared) proposing 3 tokens per step."""
    eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                 block_size=8, prefill_chunk=8)
    return eng.attach_draft(eng.make_draft(model, num_layers=1),
                            spec_tokens=3)


def _pool_invariants(kv):
    st = kv.stats()
    owned = sum(len(b) for b in kv._owned.values())
    shared = sum(len(b) for b in kv._shared.values())
    # physical partition: every block is free, privately owned, or
    # held by the prefix radix tree (aliased shared blocks live in
    # the tree, counted once however many slots map them)
    assert st["blocks_free"] + owned + st["blocks_cached"] \
        == kv.num_blocks
    assert st["blocks_reserved"] == sum(kv._reserved.values())
    assert st["blocks_available"] >= 0
    mapped = int((kv.block_tables >= 0).sum())
    assert mapped == owned + shared
    # private blocks are exclusive; aliasing may repeat a PHYSICAL
    # block across slots but never within one slot's table
    privs = [b for blks in kv._owned.values() for b in blks]
    assert len(set(privs)) == len(privs)
    for row in kv.block_tables:
        row = row[row >= 0]
        assert len(set(row.tolist())) == len(row)
    kv.check_invariants()


class TestSpecBitEquality:
    def test_server_stream_bit_equal_across_bucketed_prompts(
            self, model, paged_ref, spec_eng):
        """Greedy spec-decode streams through the GenerationServer
        match the non-speculative paged streams token-for-token for
        prompts spanning the prefill buckets; both pools drain clean
        afterwards (accept/rollback leaks nothing)."""
        srv = GenerationServer(spec_eng)
        try:
            for prompt in ([5, 9, 11, 3], [2],
                           [1, 2, 3, 4, 5, 6, 7, 8],
                           list(range(1, 14)), list(range(3, 33))):
                want = paged_ref(prompt, 12)
                got = srv.generate(prompt, 12, timeout=180)
                assert got == want, (len(prompt), got, want)
        finally:
            assert srv.shutdown(drain=True, timeout=120)
        _pool_invariants(spec_eng._kv)
        _pool_invariants(spec_eng._draft._kv)
        assert spec_eng._kv.stats()["blocks_used"] == 0
        assert spec_eng._draft._kv.stats()["blocks_used"] == 0

    def test_spec_step_rejection_rolls_back_with_invariants(
            self, model, paged_ref, spec_eng):
        """Driving spec_step directly: the committed stream continues
        the reference exactly while the allocator invariants (no
        double-ownership, reservation balance, no aliasing) hold
        after EVERY window — including the constant mid-window
        rejections a random 1-layer draft produces."""
        from paddle_tpu.observability import metrics as om

        prompt = [5, 9, 11, 3]
        want = paged_ref(prompt, 16)
        out = [spec_eng.prefill(0, prompt, budget=20)]
        before = dict(om.snapshot().get("serving", {}))
        rejected_windows = 0
        while len(out) < 16:
            toks, counts = spec_eng.spec_step()
            m = int(counts[0])
            if m < spec_eng._spec_k:
                rejected_windows += 1
            out.extend(int(t) for t in toks[0, :m])
            _pool_invariants(spec_eng._kv)
            _pool_invariants(spec_eng._draft._kv)
        spec_eng.release(0)
        assert out[:16] == want, (out, want)
        after = dict(om.snapshot().get("serving", {}))
        steps = after.get("spec_steps_total", 0) - \
            before.get("spec_steps_total", 0)
        assert steps >= 1
        # per-step counters moved: proposed = k * steps, and the
        # rejections above rolled real blocks back
        assert after.get("spec_proposed_total", 0) - \
            before.get("spec_proposed_total", 0) == \
            spec_eng._spec_k * steps
        if rejected_windows:
            assert after.get("spec_rolled_back_total", 0) >= \
                before.get("spec_rolled_back_total", 0)
        _pool_invariants(spec_eng._kv)
        assert spec_eng._kv.stats()["blocks_used"] == 0

    def test_capacity_fallback_mixes_plain_and_spec_steps(
            self, model, paged_ref):
        """When an active slot is within spec_k of capacity the server
        drops to plain single-token steps for that iteration (the
        draft cache develops holes — its proposals degrade, but the
        target's verify stays authoritative), then resumes
        speculating: the stream stays bit-correct through the mix."""
        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=40,
                                     block_size=8, prefill_chunk=8)
        eng.attach_draft(eng.make_draft(model, num_layers=1),
                         spec_tokens=4)
        srv = GenerationServer(eng)
        try:
            prompt = [5, 9, 11, 3]
            want = paged_ref(prompt, 30)
            got = srv.generate(prompt, 30, timeout=180)
            # capacity (max_seq 40) may cut the stream short; every
            # delivered token must continue the reference exactly
            assert len(got) >= 25
            assert got == want[:len(got)], (got, want)
        finally:
            assert srv.shutdown(drain=True, timeout=120)
        assert eng._kv.stats()["blocks_used"] == 0
        assert eng._draft._kv.stats()["blocks_used"] == 0

    def test_draft_shares_target_weights(self, model, spec_eng):
        """make_draft is a truncated-layer VIEW: every retained weight
        is the target's own device array, never a copy."""
        draft = spec_eng._draft
        assert draft.n_layers == 1
        assert draft.params["emb"] is spec_eng.params["emb"]
        assert draft.params["head"] is spec_eng.params["head"]
        assert draft.params["layers"][0]["q_proj"] is \
            spec_eng.params["layers"][0]["q_proj"]

    def test_attach_draft_requires_idle_engine(self, model):
        """A request admitted BEFORE attachment has no spec_k margin
        and no mirrored draft slot — attaching then would exhaust
        mid-decode, so attach_draft refuses until the engine drains."""
        eng = PagedLlamaDecodeEngine(model, max_slots=1, max_seq=64,
                                     block_size=8)
        eng.prefill(0, [1, 2, 3], budget=8)
        with pytest.raises(ValueError, match="IDLE"):
            eng.attach_draft(eng.make_draft(model, num_layers=1),
                             spec_tokens=2)
        eng.release(0)
        eng.attach_draft(eng.make_draft(model, num_layers=1),
                         spec_tokens=2)
        assert eng.generate([1, 2, 3], max_new_tokens=4)  # now fine

    def test_admission_reserves_spec_margin(self, model):
        """With a draft attached, admission reserves spec_k extra
        tokens of budget so window pre-extension can never out-draw
        the reservation."""
        eng = PagedLlamaDecodeEngine(model, max_slots=1, max_seq=64,
                                     block_size=8, num_blocks=8)
        eng.attach_draft(eng.make_draft(model, num_layers=1),
                         spec_tokens=3)
        assert eng.begin_request(0, [1, 2, 3], 8)
        # 3 prompt tokens -> 1 block now; 3+8+3=14 tokens -> 2 blocks
        # total reserved beyond the mapped one
        assert eng._kv.stats()["blocks_reserved"] == 1
        assert eng._draft._kv.stats()["blocks_reserved"] == 1
        eng.release(0)


class TestTruncateRollback:
    def test_truncate_recredits_reservation(self):
        kv = PagedKVCache(max_slots=2, max_seq=64, block_size=8,
                          num_blocks=8)
        assert kv.admit(0, 4, 40)          # 1 mapped + 4 reserved
        kv.ensure_token(0, 8)
        kv.ensure_token(0, 16)             # 2 drawn from reservation
        assert kv.stats()["blocks_used"] == 3
        assert kv.stats()["blocks_reserved"] == 2
        rolled = kv.truncate(0, 9)         # keep positions [0, 9)
        assert rolled == 1
        st = kv.stats()
        assert st["blocks_used"] == 2
        assert st["blocks_reserved"] == 3  # re-credited
        assert st["blocks_free"] >= st["blocks_reserved"]
        kv.ensure_token(0, 16)             # re-draw after rollback
        assert kv.stats()["blocks_used"] == 3
        kv.release(0)
        st = kv.stats()
        assert st["blocks_used"] == 0 and st["blocks_reserved"] == 0
        assert (kv.block_tables == -1).all()

    def test_truncate_noops(self):
        kv = PagedKVCache(max_slots=2, max_seq=64, block_size=8,
                          num_blocks=8)
        assert kv.truncate(0, 8) == 0      # nothing admitted
        kv.admit(1, 8, 8)
        assert kv.truncate(1, 8) == 0      # nothing past the kept end
        kv.release(1)


class TestPagedAttentionKernelSeam:
    """Kernel-vs-oracle parity at the flat seam, via the Pallas
    interpreter on CPU (skipped where Pallas is unavailable)."""

    def _geometries(self):
        # (S, T, H, KVH, D, block_size, max_blocks)
        return [
            (2, 1, 4, 2, 8, 8, 4),     # decode step, GQA
            (3, 5, 4, 2, 8, 8, 4),     # verify window, GQA
            (2, 4, 4, 4, 16, 4, 6),    # MHA (n_rep=1), small blocks
            (1, 8, 2, 1, 8, 16, 2),    # single slot, deep tiles
        ]

    def _case(self, S, T, H, K, D, bs, MB, quant, seed):
        import jax.numpy as jnp
        from paddle_tpu.serving_cache import (absmax_quantize,
                                              paged_attention)
        rng = np.random.default_rng(seed)
        NB = S * MB + 2
        q = jnp.asarray(rng.standard_normal((S, T, H, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((NB, bs, K * D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((NB, bs, K * D)),
                         jnp.float32)
        tables = rng.permutation(NB)[:S * MB].reshape(S, MB)
        tables = jnp.asarray(tables.astype(np.int32))
        tables = tables.at[0, MB - 1].set(-1)   # unmapped tail
        pos = jnp.asarray(
            rng.integers(0, bs * MB - T, (S, 1)).astype(np.int32)
            + np.arange(T, dtype=np.int32)[None, :])
        kw = dict(block_size=bs, n_rep=H // K)
        if quant:
            kq, ks = absmax_quantize(kp.reshape(NB * bs, K, D))
            vq, vs = absmax_quantize(vp.reshape(NB * bs, K, D))
            kw.update(k_scale=ks.reshape(NB, bs, K),
                      v_scale=vs.reshape(NB, bs, K))
            kp = kq.reshape(NB, bs, K * D)
            vp = vq.reshape(NB, bs, K * D)
        return q, kp, vp, tables, pos, kw

    def test_kernel_matches_jnp_walk_on_every_geometry(self):
        from paddle_tpu.ops.pallas import paged_attention as pk
        import jax.numpy as jnp
        from paddle_tpu.serving_cache import paged_attention
        for i, geo in enumerate(self._geometries()):
            for quant in (False, True):
                q, kp, vp, tables, pos, kw = self._case(
                    *geo, quant=quant, seed=i)
                ref = paged_attention(q, kp, vp, tables, pos,
                                      use_kernel=False, **kw)
                got = pk.paged_attention_kernel(
                    q, kp, vp, tables, pos, interpret=True, **kw)
                np.testing.assert_allclose(
                    np.asarray(ref), np.asarray(got), rtol=1e-6,
                    atol=1e-6, err_msg=f"geometry {geo} quant={quant}")

    # id: (S, T, H, KVH, D, block_size, max_blocks, pool dtype)
    DENSE = {
        "decode_gqa": (3, 1, 4, 2, 8, 8, 4, "float32"),
        "verify_window_gqa": (2, 5, 8, 2, 16, 4, 6, "float32"),
        "one_kv_head": (2, 3, 4, 1, 8, 8, 3, "float32"),
        "no_shared_heads": (2, 1, 4, 4, 16, 4, 5, "float32"),
        "yi_heads_bf16": (2, 1, 32, 4, 128, 16, 3, "bfloat16"),
        "int8_codes": (2, 2, 4, 2, 8, 8, 4, "int8"),
    }

    @pytest.mark.parametrize("name", sorted(DENSE))
    def test_walk_on_a_flat_pool_matches_dense_softmax_attention(self,
                                                                 name):
        """The oracle itself, held to something a pool's layout cannot
        fool: K and V are drawn ``[S, L, KVH, D]``, one history a slot,
        written a token at a time into a flat ``[NB, bs, KVH*D]`` pool
        through a shuffled table by the engine's own scatter, and the
        walk has to give what a plain softmax attention over the
        ``[S, L, KVH, D]`` arrays gives, query head ``h`` against KV
        head ``h // n_rep``."""
        import jax.numpy as jnp
        from paddle_tpu.serving_cache import (absmax_quantize,
                                              paged_attention,
                                              write_kv_tokens)
        S, T, H, K, D, bs, MB, dtype = self.DENSE[name]
        rng = np.random.default_rng(sorted(self.DENSE).index(name))
        quant = dtype == "int8"
        qdt = jnp.float32 if quant else jnp.dtype(dtype)
        L, NB, R = bs * MB, S * MB + 3, H // K
        q = jnp.asarray(rng.standard_normal((S, T, H, D)), qdt)
        k = jnp.asarray(rng.standard_normal((S, L, K, D)), qdt)
        v = jnp.asarray(rng.standard_normal((S, L, K, D)), qdt)
        tables = rng.permutation(NB)[:S * MB].reshape(S, MB).astype(
            np.int32)
        phys = jnp.asarray(np.repeat(tables, bs, axis=1).reshape(-1))
        off = jnp.asarray(np.tile(np.arange(L) % bs, S))
        kw = dict(block_size=bs, n_rep=R)
        kf, vf = k.reshape(S * L, K, D), v.reshape(S * L, K, D)
        if quant:
            (kf, ks), (vf, vs) = absmax_quantize(kf), absmax_quantize(vf)
            # what the pool holds is the codes times their scales
            k = (kf.astype(qdt) * ks[..., None]).reshape(k.shape)
            v = (vf.astype(qdt) * vs[..., None]).reshape(v.shape)
            zeros = jnp.zeros((NB, bs, K), jnp.float32)
            kw.update(k_scale=write_kv_tokens(zeros, phys, off, ks),
                      v_scale=write_kv_tokens(zeros, phys, off, vs))
        # never-written blocks hold what a recycled block may hold
        garbage = jnp.full((NB, bs, K * D), 99, jnp.dtype(dtype))
        kp = write_kv_tokens(garbage, phys, off, kf.reshape(S * L, -1))
        vp = write_kv_tokens(garbage, phys, off, vf.reshape(S * L, -1))
        last = rng.integers(T - 1, L, (S,))
        last[0] = L - 1
        pos = (last[:, None] - (T - 1) + np.arange(T)[None, :]).astype(
            np.int32)
        got = np.asarray(paged_attention(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(pos),
            use_kernel=False, **kw), np.float64)
        q64, k64, v64 = (np.asarray(a, np.float64) for a in (q, k, v))
        want = np.zeros_like(got)
        for s_, t, h in np.ndindex(S, T, H):
            n = pos[s_, t] + 1
            sco = k64[s_, :n, h // R] @ q64[s_, t, h] / np.sqrt(D)
            p = np.exp(sco - sco.max())
            want[s_, t, h] = (p / p.sum()) @ v64[s_, :n, h // R]
        tol = 2e-2 if dtype == "bfloat16" else 1e-5
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    # id: (S, T, H, KVH, D, block_size, first positions, pool dtype,
    #      extras). The table holds one group of blocks and four more
    # (a count the group does not divide); a position is a number or
    # "block-2" (a history of one block less one token), "group-1" |
    # "group" (a group's last | the next group's first column), "last"
    # (the window ends at max_seq - 1)
    RAGGED = {
        "ragged_slots_T1": (5, 1, 4, 2, 8, 16,
                            [0, "block-2", "group-1", "group", "last"],
                            "float32", {}),
        "small_blocks_f32": (3, 1, 4, 2, 8, 8, ["last", 100, "group"],
                             "float32", {}),
        "yi_geometry_bf16": (3, 1, 32, 4, 128, 16, [5, 200, "last"],
                             "bfloat16", {}),
        "yi_geometry_f32": (2, 1, 32, 4, 128, 16, [17, "group"],
                            "float32", {}),
        "mistral_geometry": (2, 1, 32, 8, 128, 16, [31, "group-1"],
                             "float32", {}),
        "verify_window_T5": (4, 5, 4, 2, 8, 16,
                             [0, 11, "group-1", "last"], "float32", {}),
        # a later prefill chunk: one slot, 64 rows from mid-block,
        # across a group's edge
        "prefill_chunk_mid_block": (1, 64, 4, 2, 8, 16, ["group-41"],
                                    "float32", {}),
        "prefill_chunk_yi_bf16": (1, 64, 32, 4, 128, 16, [23],
                                  "bfloat16", {}),
        "int8_pools_ragged": (4, 1, 4, 2, 8, 16,
                              [0, "block-2", "group-1", "last"],
                              "float32", {"quant": True}),
        "int8_pools_verify": (2, 5, 4, 2, 8, 16, ["group-1", 40],
                              "float32", {"quant": True}),
        # entries past each slot's history unmapped; slots 0 and 1
        # share their first three blocks (a shared prefix)
        "unmapped_and_shared_tables": (
            3, 1, 4, 2, 8, 16, [60, "group", 0], "float32",
            {"unmap_tail": True, "share": 3}),
        # the caller stops the walk short of a slot's own bound: the
        # columns past it are not attended, on either path
        "n_tiles_below_a_slots_bound": (
            3, 1, 4, 2, 8, 16, ["last", 40, "group"], "float32",
            {"n_tiles": 3}),
        "n_tiles_one_block_short": (
            3, 1, 4, 2, 8, 16, ["last", 40, "group"], "float32",
            {"n_tiles": -1}),
        # a slot's first group is started while the slot before it is
        # computed, into the half that slot's last group leaves free:
        # "groups" tables of that many groups and four blocks, so slots
        # of 1 to 4 groups sit side by side in every order
        "slots_5": (
            5, 1, 4, 2, 8, 16, ["last", 7, "group", 100, "group*2"],
            "float32", {"groups": 3}),
        "slots_33": (
            33, 1, 4, 2, 8, 16,
            [0, "group-1", "group", 40, "last", 300, "group*2"] * 4
            + [9, "last", 0, "group", 511], "float32", {"groups": 2}),
        "slots_yi_bf16_9": (
            9, 1, 32, 4, 128, 16,
            [5, 200, "last", 0, 300, "group", 17, 40, "group-1"],
            "bfloat16", {}),
        "slots_position_0_among_long_slots": (
            4, 1, 4, 2, 8, 16, ["last", 0, "last", "group*2"], "float32",
            {"groups": 3}),
        # every block count 0: no copy is started, zeros come out
        "slots_no_block_at_all": (
            5, 1, 4, 2, 8, 16, ["last", 0, "group", 100, 3], "float32",
            {"n_tiles": 0}),
        "slots_many_groups_then_one": (
            6, 1, 4, 2, 8, 16,
            ["group*3-1", 5, "last", 5, "group*2", 9], "float32",
            {"groups": 3}),
        "slots_one_group_then_many": (
            6, 1, 4, 2, 8, 16,
            [5, "last", 9, "group*3-1", 3, "group*2"], "float32",
            {"groups": 3}),
        "slots_verify_rows_mixed_groups": (
            5, 5, 4, 2, 8, 16, ["group*2", 0, "last", "group-1", 11],
            "float32", {"groups": 3}),
        # wide slots (over _NARROW_ROWS query rows a KV head, as a
        # chunk's tiles are) keep the plain starts and a wait a block
        "slots_wide_rows_mixed_groups": (
            4, 20, 4, 2, 8, 16, ["group*2", 0, "last", 700], "float32",
            {"groups": 3}),
        # a window layer's walk starts at the slot's first live block
        "slots_window_many_groups_then_one": (
            6, 1, 4, 2, 8, 16,
            ["group*3-1", 5, "last", 5, "group*2", 9], "float32",
            {"groups": 3, "window": 1100}),
        "slots_window_one_group_then_many": (
            6, 1, 4, 2, 8, 16,
            [5, "last", 9, "group*3-1", 3, "group*2"], "float32",
            {"groups": 3, "window": 300}),
        # a slot whose rows see nothing (their first visible position
        # lies past them) has no group: it starts the next slot's first
        # copy in its stead
        "slots_window_slots_that_see_nothing": (
            6, 1, 4, 2, 8, 16,
            [5, "last", 9, "group*3-1", 3, "group*2"], "float32",
            {"groups": 3, "window": 300, "sees_nothing": [0, 3, 4]}),
        "slots_int8_many_groups_then_one": (
            6, 1, 4, 2, 8, 16,
            ["group*3-1", 5, "last", 5, "group*2", 9], "float32",
            {"groups": 3, "quant": True}),
        "slots_int8_one_group_then_many": (
            6, 1, 4, 2, 8, 16,
            [5, "last", 9, "group*3-1", 3, "group*2"], "float32",
            {"groups": 3, "quant": True}),
    }

    def _ragged_case(self, name):
        """(q, pools, tables, positions, the seam's keywords) of a
        RAGGED case, seeded by its place in the list."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import paged_attention as pk
        from paddle_tpu.serving_cache import absmax_quantize
        S, T, H, K, D, bs, first, dtype, extra = self.RAGGED[name]
        rng = np.random.default_rng(sorted(self.RAGGED).index(name))
        dt = jnp.dtype(dtype)
        quant = bool(extra.get("quant"))
        geo = (bs, K * D, jnp.int8 if quant else dt, T, H // K)
        group = pk.group_tokens(*geo, 1 << 20, quant)
        MB = extra.get("groups", 1) * group // bs + 4
        assert group == pk.group_tokens(*geo, MB, quant)
        assert bs < group < bs * MB, "several blocks, several groups"
        where = {"block-2": bs - 2, "group-1": group - 1, "group": group,
                 "group-41": group - 41, "group*2": 2 * group,
                 "group*3-1": 3 * group - 1, "last": bs * MB - T}
        assert len(first) == S
        pos = (np.asarray([where.get(f, f) for f in first],
                          np.int32)[:, None]
               + np.arange(T, dtype=np.int32)[None, :])
        assert 0 <= pos.min() and pos.max() < bs * MB
        NB = S * MB + 3
        q = jnp.asarray(rng.standard_normal((S, T, H, D)), dt)
        kp = jnp.asarray(rng.standard_normal((NB, bs, K * D)), dt)
        vp = jnp.asarray(rng.standard_normal((NB, bs, K * D)), dt)
        tables = rng.permutation(NB)[:S * MB].reshape(S, MB).astype(
            np.int32)
        if extra.get("share"):
            tables[1, :extra["share"]] = tables[0, :extra["share"]]
        if extra.get("unmap_tail"):
            for s_ in range(S):
                tables[s_, pos[s_].max() // bs + 1:] = -1
        kw = dict(block_size=bs, n_rep=H // K)
        if "n_tiles" in extra:
            kw["n_tiles"] = jnp.asarray(extra["n_tiles"] % (MB + 1),
                                        jnp.int32)
        if "window" in extra:
            lower = pos - (extra["window"] - 1)
            for s_ in extra.get("sees_nothing", ()):
                lower[s_] = pos[s_] + 2 * bs
            kw["lower"] = jnp.asarray(lower)
            # blocks wholly behind a slot's window are gone, as the
            # table frees them
            for s_ in range(S):
                tables[s_, :max(int(pos[s_, 0]) - extra["window"] + 1,
                                0) // bs] = -1
        if quant:
            kq, ks = absmax_quantize(kp.reshape(NB * bs, K, D))
            vq, vs = absmax_quantize(vp.reshape(NB * bs, K, D))
            kw.update(k_scale=ks.reshape(NB, bs, K),
                      v_scale=vs.reshape(NB, bs, K))
            kp, vp = kq.reshape(kp.shape), vq.reshape(vp.shape)
        return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos), kw

    @pytest.mark.parametrize("name", sorted(RAGGED))
    def test_kernel_matches_jnp_walk_on_ragged_batches(self, name):
        """Each slot walks its own blocks only, a group at a time:
        every way a slot's history can sit against the groups, and a
        slot against the slot before it (whose last group's arithmetic
        its first copies run under), at the served geometries, agrees
        with the jnp walk."""
        from paddle_tpu.ops.pallas import paged_attention as pk
        from paddle_tpu.serving_cache import paged_attention
        dtype, extra = self.RAGGED[name][7:]
        q, kp, vp, tables, pos, kw = self._ragged_case(name)
        ref = paged_attention(q, kp, vp, tables, pos, use_kernel=False,
                              **kw)
        got = pk.paged_attention_kernel(q, kp, vp, tables, pos,
                                        interpret=True, **kw)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        if extra.get("n_tiles") == 0:
            assert not np.asarray(got, np.float32).any()
        for s_ in extra.get("sees_nothing", ()):
            assert not np.asarray(got[s_], np.float32).any()
        # bfloat16: both paths round p and the output to 8 bits, a
        # group at a time against a block at a time
        tol = 2e-2 if dtype == "bfloat16" else 1e-5
        np.testing.assert_allclose(
            np.asarray(ref, np.float32), np.asarray(got, np.float32),
            rtol=tol, atol=tol)

    @pytest.mark.parametrize("name", sorted(RAGGED))
    def test_kernel_waits_for_the_bytes_it_started(self, name):
        """`interpret=True` copies at a DMA's start and takes every wait
        as done, so it cannot see a wait that names the wrong bytes, nor
        a start that comes after the wait for it. `InterpretParams`
        counts a semaphore's bytes as the chip does and moves the data
        at the wait: with a wait of one block too many the call blocks
        for ever, with one too few the walk's answer is missed by 2.9
        (both tried by hand on `slots_many_groups_then_one`). Every
        ragged case, so a narrow slot's waits by binary digit and its
        starts from inside the arithmetic as well as a wide slot's plain
        loops, int8 scale streams and window walks included; the call
        runs in a thread so that a wait nothing satisfies fails the
        test and does not hang the run."""
        import threading
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.ops.pallas import paged_attention as pk
        from paddle_tpu.serving_cache import paged_attention
        dtype = self.RAGGED[name][7]
        q, kp, vp, tables, pos, kw = self._ragged_case(name)
        ref = paged_attention(q, kp, vp, tables, pos, use_kernel=False,
                              **kw)
        got = []
        call = threading.Thread(daemon=True, target=lambda: got.append(
            np.asarray(pk.paged_attention_kernel(
                q, kp, vp, tables, pos,
                interpret=pltpu.InterpretParams(), **kw), np.float32)))
        call.start()
        call.join(300)
        assert got, "a wait that no copy satisfies"
        tol = 2e-2 if dtype == "bfloat16" else 1e-5
        np.testing.assert_allclose(np.asarray(ref, np.float32), got[0],
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize(
        "name", sorted(n for n in RAGGED if n.startswith("slots_")))
    def test_a_slot_among_others_gets_bit_for_bit_what_it_gets_alone(
            self, name):
        """A call of one slot starts and waits for its own first copy.
        Among others a slot's first group is started by the slot before
        it, and its groups land in whichever half that slot left them;
        they come in the same order through the same arithmetic, so its
        output is the same to the bit."""
        from paddle_tpu.ops.pallas import paged_attention as pk
        q, kp, vp, tables, pos, kw = self._ragged_case(name)
        lower = kw.pop("lower", None)
        got = np.asarray(pk.paged_attention_kernel(
            q, kp, vp, tables, pos, lower=lower, interpret=True, **kw),
            np.float32)
        for s_ in range(q.shape[0]):
            alone = pk.paged_attention_kernel(
                q[s_:s_ + 1], kp, vp, tables[s_:s_ + 1], pos[s_:s_ + 1],
                lower=None if lower is None else lower[s_:s_ + 1],
                interpret=True, **kw)
            assert np.array_equal(got[s_], np.asarray(alone[0],
                                                      np.float32)), s_

    def test_group_size_comes_from_static_shapes(self):
        """What a group is at the shapes that are served, and where it
        falls back to one block: blocks that do not stack into a tile
        for free, or a block that is a group already."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import paged_attention as pk
        bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
        # (bs, KVH*D, pool dtype, T, n_rep, max_blocks, dequant) -> C
        for args, want in [
                ((16, 512, bf16, 1, 8, 128, False), 32),   # Yi decode
                ((16, 512, bf16, 64, 8, 128, False), 32),  # Yi chunk
                ((16, 1024, bf16, 1, 4, 128, False), 32),  # Mistral
                ((16, 512, i8, 1, 8, 128, True), 32),      # int8 pool
                ((128, 512, bf16, 1, 8, 16, False), 4),    # 128-token blocks
                ((16, 512, bf16, 1, 8, 3, False), 3),      # short table
                ((8, 512, bf16, 1, 8, 128, False), 1),     # half a tile
                ((4, 512, f32, 1, 8, 128, False), 1),
                ((8, 512, f32, 1, 8, 128, False), 64),
                ((16, 4096, f32, 1, 1, 128, False), 4),    # wide K/V
                ((16, 512, bf16, 256, 8, 128, False), 8)]:  # wide scores
            assert pk.group_blocks(*args) == want, (args, want)
        assert pk.group_tokens(16, 512, bf16, 1, 8, 128) == 512

    @pytest.mark.parametrize("T, n_rep, narrow", [
        (1, 8, True),        # Yi decode
        (1, 16, True),       # Command A+ decode: the last narrow shape
        (5, 8, False),       # the verify window
        (8, 8, False),       # Yi's chunk of 8 rows
        (64, 8, False),      # Yi's chunk of 64 rows
        (64, 16, False)])    # Command A+'s 64-row tiles
    def test_fast_starts_and_waits_come_from_the_rows_a_kv_head(
            self, T, n_rep, narrow):
        """What chooses between the two ways a group's copies are
        started and waited for is a static shape, the query rows a KV
        head: a narrow slot (a decode call) gets starts of four a pass,
        half a group's under the arithmetic and so two copies of it,
        and waits by the binary digits of the block count; a wide one
        (a prompt chunk) the plain loops and one copy of the
        arithmetic. Counted in the jaxpr of the kernel."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import paged_attention as pk
        assert (T * n_rep <= pk._NARROW_ROWS) == narrow
        S, K, D, bs, NB, MB = 2, 1, 8, 16, 64, 64
        sds = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, t, p: pk.paged_attention_kernel(
                q, k, v, t, p, block_size=bs, n_rep=n_rep,
                interpret=True))(
            sds((S, T, K * n_rep, D), jnp.float32),
            sds((NB, bs, K * D), jnp.float32),
            sds((NB, bs, K * D), jnp.float32),
            sds((S, MB), jnp.int32), sds((S, T), jnp.int32))
        count = {}

        def find(j):
            for e in j.eqns:
                count[e.primitive.name] = count.get(e.primitive.name, 0) + 1
                for v in e.params.values():
                    for x in (v if isinstance(v, (list, tuple)) else [v]):
                        x = getattr(x, "jaxpr", x)
                        if hasattr(x, "eqns"):
                            find(x)
        find(jaxpr.jaxpr)
        C = pk.group_blocks(bs, K * D, jnp.float32, T, n_rep, MB)
        assert C >= 16
        # a dot for the scores and one for PV, a copy of the arithmetic
        assert count["dot_general"] == (4 if narrow else 2)
        # K and V: a wait a binary digit of the count, or one in a loop
        assert count["dma_wait"] == (2 * C.bit_length() if narrow else 2)
        # starts: a loop's one (K and V) for the first slot, the
        # hand-on and a group; a narrow slot's groups also a pass of
        # four, and the arithmetic a pass of half a group's
        assert count["dma_start"] == (3 * 2 + 2 + 2 if narrow else 3 * 2)

    def test_kernel_sanitizes_recycled_garbage(self):
        """The MASKED-garbage contract, kernel side: an unmapped
        table entry (-1) clamps its gather to physical block 0 — fill
        block 0 with NaN/inf and keep every position below the
        unmapped tile, and the clamped garbage must contribute
        exactly zero (finite output, bit-matching the jnp walk's
        sanitized result)."""
        from paddle_tpu.ops.pallas import paged_attention as pk
        import jax.numpy as jnp
        from paddle_tpu.serving_cache import paged_attention
        rng = np.random.default_rng(9)
        S, T, H, K, D, bs, MB = 2, 1, 4, 2, 8, 8, 4
        NB = S * MB + 2
        q = jnp.asarray(rng.standard_normal((S, T, H, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((NB, bs, K * D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((NB, bs, K * D)),
                         jnp.float32)
        # block 0 is nobody's block: tables draw from [1, NB), the
        # last logical tile of each slot is unmapped (-1 -> clamps to
        # the poisoned block 0), and positions stop before that tile
        tables = 1 + rng.permutation(NB - 1)[:S * MB].reshape(S, MB)
        tables = jnp.asarray(tables.astype(np.int32))
        tables = tables.at[:, MB - 1].set(-1)
        pos = jnp.asarray(
            rng.integers(0, bs * (MB - 1) - T, (S, 1)).astype(np.int32)
            + np.arange(T, dtype=np.int32)[None, :])
        kp = kp.at[0].set(jnp.nan)
        vp = vp.at[0].set(jnp.inf)
        kw = dict(block_size=bs, n_rep=H // K)
        ref = paged_attention(q, kp, vp, tables, pos,
                              use_kernel=False, **kw)
        got = pk.paged_attention_kernel(q, kp, vp, tables, pos,
                                        interpret=True, **kw)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=1e-6, atol=1e-6)

    def test_seam_chooses_by_platform_and_head_width(self, monkeypatch):
        """The seam's choice, from what it can observe. On a TPU the
        kernel runs where head_dim fills whole 128-lane registers and
        the jnp walk runs below that — Mosaic refuses the kernel's
        [bs, KVH*D] -> [bs, KVH, D] view at head_dim 16/32/64 (rule
        found by compiling against a v5e topology; see
        ops.pallas.paged_attention.kernel_available). Off the TPU,
        always the walk. The walk taken is counted."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu import serving_cache as sc
        from paddle_tpu.observability import metrics as om

        assert sc.use_kernel_default(128) is False        # CPU host
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert [d for d in (16, 32, 64, 128, 256)
                if sc.use_kernel_default(d)] == [128, 256]
        # a tiny-width engine on the "TPU" takes the walk, and says so
        walk = om.default_registry().get("pallas.path_selected_total")
        before = walk.value(kernel="paged_attention", path="jnp_walk")
        S, T, H, K, D, bs, MB = 1, 1, 2, 1, 16, 8, 2
        q = jnp.ones((S, T, H, D), jnp.float32)
        pool = jnp.ones((MB, bs, K * D), jnp.float32)
        out = sc.paged_attention(
            q, pool, pool, jnp.arange(MB, dtype=jnp.int32)[None],
            jnp.full((S, T), 5, jnp.int32), block_size=bs, n_rep=H // K)
        np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-6)
        assert walk.value(kernel="paged_attention",
                          path="jnp_walk") == before + 1

    def test_seam_takes_one_pool_layout(self):
        """Flat pools only: a pool with its heads apart would cost the
        kernel a copy of the whole pool a launch, so the seam refuses
        it on both paths."""
        import jax.numpy as jnp
        from paddle_tpu import serving_cache as sc
        S, T, H, K, D, bs, MB = 1, 1, 4, 2, 8, 8, 2
        q = jnp.ones((S, T, H, D), jnp.float32)
        pool = jnp.ones((MB, bs, K, D), jnp.float32)
        for use_kernel in (False, True):
            with pytest.raises(ValueError, match="KVH\\*D"):
                sc.paged_attention(
                    q, pool, pool, jnp.arange(MB, dtype=jnp.int32)[None],
                    jnp.full((S, T), 5, jnp.int32), block_size=bs,
                    n_rep=H // K, use_kernel=use_kernel)


class TestJaxprPins:
    def _walk_shapes(self, jaxpr):
        import jax.extend.core as jcore
        shapes = []

        def walk(jx):
            for eqn in jx.eqns:
                for v in eqn.outvars:
                    shapes.append(
                        (eqn.primitive.name,
                         tuple(getattr(v.aval, "shape", ()))))
                for p in eqn.params.values():
                    for sub in (p if isinstance(p, (list, tuple))
                                else [p]):
                        if isinstance(sub, jcore.Jaxpr):
                            walk(sub)
                        elif isinstance(sub, jcore.ClosedJaxpr):
                            walk(sub.jaxpr)

        walk(jaxpr.jaxpr)
        return shapes

    def test_spec_verify_no_dense_view(self, model, spec_eng):
        """The batched verify step obeys the same pin as the decode
        step: no [*, max_seq]-shaped intermediate anywhere (max_seq
        64 collides with CFG's vocab_size — use a 48-token engine)."""
        import jax
        import jax.numpy as jnp

        max_seq = 48
        eng = PagedLlamaDecodeEngine(model, max_slots=2,
                                     max_seq=max_seq, block_size=16)
        eng.attach_draft(eng.make_draft(model, num_layers=1),
                         spec_tokens=3)
        k = eng._spec_k
        args = (eng.params, eng.kvs, jnp.asarray(eng.last_ids),
                jnp.zeros((2, k), jnp.int32), jnp.asarray(eng.pos),
                jnp.asarray(eng._kv.block_tables),
                jnp.asarray(eng.active))
        jaxpr = jax.make_jaxpr(eng._spec_verify_impl)(*args)
        offenders = [(p, s) for p, s in self._walk_shapes(jaxpr)
                     if max_seq in s]
        assert offenders == [], offenders

    def test_kernel_path_jaxpr_no_dense_view(self, model,
                                             monkeypatch):
        """The acceptance pin holds on the KERNEL path too: with the
        seam forced to the Pallas kernel, the paged decode step's
        jaxpr (pallas_call inner jaxpr included) still carries no
        [*, max_seq]-shaped intermediate. (The kernel computes on a
        group of blocks at a time, 512 tokens here, so max_seq has to
        be longer than a group for the pin to say anything.)"""
        import jax
        import jax.numpy as jnp
        from paddle_tpu import serving_cache
        monkeypatch.setattr(serving_cache, "use_kernel_default",
                            lambda head_dim: True)
        max_seq = 1040
        eng = PagedLlamaDecodeEngine(model, max_slots=3,
                                     max_seq=max_seq, block_size=16)
        args = (eng.params, eng.kvs, jnp.asarray(eng.last_ids),
                jnp.asarray(eng.pos),
                jnp.asarray(eng._kv.block_tables),
                jnp.asarray(eng.active))
        jaxpr = jax.make_jaxpr(eng._decode_impl)(*args)
        offenders = [(p, s) for p, s in self._walk_shapes(jaxpr)
                     if max_seq in s]
        assert offenders == [], offenders


    @pytest.mark.parametrize("S", [32, 33])
    def test_kernel_grid_is_one_step_a_slot_at_the_cells_geometry(self, S):
        """The serving cell's decode call (32 slots, 128-entry tables
        of 16-token blocks, Yi's heads, bf16 pool of 4096 blocks; and
        with a slot more): the pallas_call walks at most an eighth of
        the (slot, table entry) steps the old grid took whatever was
        live (one a slot, in fact: a tile of slots a step was measured
        on the chip and bought nothing, PERF.md section 6, PR 34), its
        pools stay where they are (no block spec brings them in), and
        nothing outside or inside it is a gather or has a max_seq-sized
        axis."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import paged_attention as pk
        T, H, K, D, bs, NB, MB = 1, 32, 4, 128, 16, 4096, 128
        sds = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, t, p, n: pk.paged_attention_kernel(
                q, k, v, t, p, block_size=bs, n_rep=H // K, n_tiles=n))(
            sds((S, T, H, D), jnp.bfloat16),
            sds((NB, bs, K * D), jnp.bfloat16),
            sds((NB, bs, K * D), jnp.bfloat16),
            sds((S, MB), jnp.int32), sds((S, T), jnp.int32),
            sds((), jnp.int32))
        calls = []

        def find(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn)
                for p in eqn.params.values():
                    inner = getattr(p, "jaxpr", p)
                    if hasattr(inner, "eqns"):
                        find(inner)

        find(jaxpr.jaxpr)
        assert len(calls) == 1
        gm = calls[0].params["grid_mapping"]
        assert gm.grid == (S,) and S <= S * MB // 8
        pools = [bm for bm in gm.block_mappings
                 if bm.array_aval.shape == (NB, bs, K * D)]
        assert len(pools) == 2
        for bm in pools:        # left in HBM, fetched by the kernel
            assert "any" in str(bm.transformed_block_aval).lower(), bm
        shapes = self._walk_shapes(jaxpr)
        assert not [p for p, _ in shapes if "gather" in p]
        assert not [(p, sh) for p, sh in shapes if bs * MB in sh]


class TestSpecCapture:
    def test_spec_step_audits_zero_syncs(self, model):
        """Steady-state speculative step: the draft-propose and
        batched-verify executables run 0 host syncs and both count
        into sot.captured_steps_total — the PR 10/11 pin extended
        over the spec pair (the window fetch + accept/rollback
        bookkeeping live OUTSIDE the audited region by design: they
        are the capture boundary, allowlisted as such)."""
        import jax.numpy as jnp
        from paddle_tpu import analysis
        from paddle_tpu.observability import metrics as om

        eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                     block_size=8)
        eng.attach_draft(eng.make_draft(model, num_layers=1),
                         spec_tokens=2)
        eng.prefill(0, [1, 2, 3], budget=30)
        eng.prefill(1, [4, 5], budget=30)
        for _ in range(2):                 # warm + steady state
            eng.spec_step()
        draft, k = eng._draft, eng._spec_k

        def one_spec_step():
            for s in range(eng.max_slots):
                if eng.active[s]:
                    eng._kv.reserve_through(s, int(eng.pos[s]) + k)
                    draft._kv.reserve_through(
                        s, int(eng.pos[s]) + k - 1)
            last = jnp.asarray(eng.last_ids)
            pos = jnp.asarray(eng.pos)
            act = jnp.asarray(eng.active)
            dtok, draft.kvs = eng._spec_propose(
                draft.params, draft.kvs, last, pos,
                jnp.asarray(draft._kv.block_tables), act)
            t, n_acc, eng.kvs = eng._spec_verify(
                eng.params, eng.kvs, last, dtok, pos,
                jnp.asarray(eng._kv.block_tables), act)
            return t, n_acc

        before = dict(om.snapshot().get("sot", {}))
        rep = analysis.audit(one_spec_step)
        after = dict(om.snapshot().get("sot", {}))
        assert rep.syncs == [], rep.syncs
        assert not [d for d in rep.diagnostics
                    if d.rule in ("PTA001", "PTA002", "PTA003")], \
            [d.to_dict() for d in rep.diagnostics]
        got = after.get("captured_steps_total", 0) - \
            before.get("captured_steps_total", 0)
        assert got >= 2, (before, after)   # propose AND verify


class TestLoadShedding:
    def test_shed_rejects_when_starved_and_backlogged(self, model):
        """ROADMAP 1c policy: pool exhausted + deferred backlog over
        FLAGS_serving_shed_queue -> submit() rejects immediately with
        reason=shed (counted + flight event) instead of deferring
        unboundedly; in-flight work is untouched and the default
        (flag 0) keeps the pre-policy defer-forever behavior."""
        from paddle_tpu.observability import flight
        from paddle_tpu.observability import metrics as om

        eng = PagedLlamaDecodeEngine(model, max_slots=4, max_seq=64,
                                     block_size=8, num_blocks=4,
                                     prefill_chunk=8)
        orig_step = eng.step_collect

        def slow_step(launch):
            time.sleep(0.02)
            return orig_step(launch)

        eng.step_collect = slow_step
        srv = GenerationServer(eng)
        try:
            # 12 prompt + 20 budget = 32 tokens = the whole 4-block
            # pool (any larger could NEVER fit and fails loudly)
            blocker = srv.submit([1, 2, 3] * 4, 20)
            deferred = [srv.submit([1, 2, 3] * 4, 8)
                        for _ in range(3)]
            for _ in range(300):               # wait for the backlog
                st = srv.stats()
                if st["waiting_for_blocks"] >= 1 \
                        and st["waiting_for_blocks"] + st["queued"] >= 2:
                    break
                time.sleep(0.02)
            st = srv.stats()
            assert st["waiting_for_blocks"] >= 1, st
            assert st["waiting_for_blocks"] + st["queued"] >= 2, st
            paddle.set_flags({"FLAGS_serving_shed_queue": 1})
            before = dict(om.snapshot().get("serving", {}))
            with pytest.raises(RuntimeError, match="shed"):
                srv.submit([7, 8, 9], 4)
            after = dict(om.snapshot().get("serving", {}))
            assert srv.stats()["shed"] == 1
            assert after.get("shed_total", 0) == \
                before.get("shed_total", 0) + 1
            sheds = [e for e in flight.events(category="serving")
                     if e["name"] == "rejected"
                     and e.get("attrs", {}).get("reason") == "shed"]
            assert sheds, "no rejected(reason=shed) flight event"
            # with the policy off, the same submit defers instead
            paddle.set_flags({"FLAGS_serving_shed_queue": 0})
            ok = srv.submit([7, 8, 9], 4)
            assert blocker["done"].wait(180) and \
                blocker["error"] is None
            for r in deferred + [ok]:
                assert r["done"].wait(180)
                assert r["error"] is None, r["error"]
        finally:
            paddle.set_flags({"FLAGS_serving_shed_queue": 0})
            srv.shutdown(drain=True, timeout=120)
        assert eng._kv.stats()["blocks_used"] == 0
