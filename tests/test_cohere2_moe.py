"""Cohere2-MoE (Command A+) through the paged serving engine, at tiny sizes
on the CPU with seeded weights: the model against the benchmark's plain
reference, the share of the experts against the uncut layer, chunked prefill
and decode through a cache of two kinds against the reference's full
forward, the allocator, the kernel's lower bound, the expert layer, the
refusals, and the counts the spans carry."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import serving_cache as sc
from paddle_tpu.incubate import moe_share
from paddle_tpu.models import Cohere2MoeConfig, Cohere2MoeForCausalLM
from paddle_tpu.models import cohere2_moe as cm
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.pallas import paged_attention as pk
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine

from benchmark.lib import reference_cohere2_moe as R
from benchmark.lib import weights_cohere2_moe as W
from benchmark.runners import serve_paged_moe as runner

TYPES = ["sliding_attention"] * 3 + ["full_attention"]


def bench_cfg(held=(0, 16), **kw):
    """A configuration file's dict at a tiny size: 16 published experts."""
    cfg = dict(hidden_size=32, head_dim=16, intermediate_size=48,
               num_attention_heads=4, num_key_value_heads=2,
               num_shared_experts=2, num_experts=held[1] - held[0],
               experts_held_from=held[0], num_experts_published=16,
               num_experts_per_tok=4, vocab_size=96, num_hidden_layers=4,
               layer_types=TYPES * 2, layer_switch=4, sliding_window=8,
               rope_theta=50000, layer_norm_eps=1e-5, logit_scale=1,
               norm_topk_prob=True, max_position_embeddings=4096,
               dtype="float32")
    cfg.update(kw)
    return cfg


def seeded_model(cfg, seed=7):
    return runner.build_model(cfg, W.seed_u32(seed), "float32")


_REF = {}


def reference_logits(cfg, ids, seed=7, pad_to=64):
    """The reference's logits at every position of `ids`. The sequence is
    padded to one length (a causal model's earlier positions never see the
    padding), so one set of programs serves every call."""
    key = (cfg["experts_held_from"], cfg["num_experts"], seed)
    if key not in _REF:
        s = W.seed_u32(seed)
        layers = [W.make_layer(cfg, jnp.float32)(s, i)
                  for i in range(cfg["num_hidden_layers"])]
        embed, norm = W.make_ends(cfg, jnp.float32)(s)
        _REF[key] = jax.jit(lambda x: R.forward_logits(
            cfg, layers, embed, norm, x, W.experts_held(cfg)))
    padded = np.zeros(max(pad_to, len(ids)), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_REF[key](jnp.asarray(padded)))[:len(ids)]


@pytest.mark.parametrize("length", [5, 19, 40])
def test_model_forward_matches_the_plain_reference(length):
    cfg = bench_cfg()
    ids = np.random.default_rng(length).integers(0, 96, length).astype(np.int32)
    got = seeded_model(cfg)(paddle.to_tensor(ids[None]))._data[0]
    ref = reference_logits(cfg, ids)
    assert np.abs(np.asarray(got) - ref).max() < 2e-5 * max(ref.std(), 1e-3) \
        + 1e-5
    assert ref.std() > 0.01        # seeded weights give logits that differ


def test_loaded_weights_are_the_references_bit_for_bit():
    cfg = bench_cfg(held=(4, 8))
    model = seeded_model(cfg)
    params = dict(model.named_parameters())
    s = W.seed_u32(7)
    for i in range(cfg["num_hidden_layers"]):
        for name, leaf in W.make_layer(cfg, jnp.float32)(s, i).items():
            mine = params[runner.program_name(f"layers.{i}.{name}")]._data
            assert np.array_equal(np.asarray(mine), np.asarray(leaf)), name
    assert params["model.layers.0.mlp.experts.down_proj"].shape[0] == 4
    assert params["model.layers.0.mlp.gate.weight"].shape[0] == 16


# -- the share ---------------------------------------------------------------

def _layer_inputs(seed=3):
    cfg = bench_cfg()
    lp = W.make_layer(cfg, jnp.float32)(W.seed_u32(seed), 1)
    n = jnp.asarray(np.random.default_rng(seed).normal(size=(24, 32)),
                    jnp.float32)
    return cfg, lp, n


def _share_of(lp, lo, hi):
    """The layer's leaves as the chip that holds experts lo..hi-1 has them."""
    return dict(lp, experts_gate_up=lp["experts_gate_up"][lo:hi],
                experts_down=lp["experts_down"][lo:hi])


def _program_share(cfg, lp, n, lo, hi):
    c = runner.model_config(cfg, "float32")
    m, counts = cm.experts_block(c, _share_of(lp, lo, hi), n, (lo, hi))
    return np.asarray(m), np.asarray(counts)


def _reference_share(cfg, lp, n, lo, hi):
    f32 = {k: v.astype(jnp.float32) for k, v in _share_of(lp, lo, hi).items()}
    return np.asarray(R.experts(f32, n, cfg, (lo, hi), "f32")[0])


@pytest.mark.parametrize("share", range(8))
def test_a_share_of_the_experts_matches_the_reference_given_the_same(share):
    cfg, lp, n = _layer_inputs()
    lo, hi = 2 * share, 2 * share + 2
    got, counts = _program_share(cfg, lp, n, lo, hi)
    assert np.abs(got - _reference_share(cfg, lp, n, lo, hi)).max() < 1e-5
    assert 0 < counts[0] < 24 * 4 and counts[1] <= 2 and counts[2] <= counts[0]


def test_all_shares_with_the_shared_experts_once_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of all 8 shares, with what
    every chip computes alike (the shared experts) counted once, equal the
    uncut reference's layer."""
    cfg, lp, n = _layer_inputs()
    shared_once = _reference_share(cfg, lp, n, 0, 0)
    whole = _reference_share(cfg, lp, n, 0, 16)
    total, rows = shared_once.copy(), 0
    for share in range(8):
        m, counts = _program_share(cfg, lp, n, 2 * share, 2 * share + 2)
        total += m - shared_once
        rows += int(counts[0])
    assert rows == 24 * 4          # every (row, expert) pair landed somewhere
    assert np.abs(total - whole).max() < 2e-5


# -- chunked prefill, then decode, through the cache of two kinds --------------

def _engine(cfg, **kw):
    args = dict(max_slots=3, max_seq=64, block_size=4, prefill_chunk=8)
    args.update(kw)
    return PagedLlamaDecodeEngine(seeded_model(cfg), **args)


def _step_logits(eng):
    """The logits the next decode step computes, without running it."""
    logits, _, _, _ = eng._forward_paged(
        eng.params, eng.kvs, jnp.asarray(eng.last_ids),
        jnp.asarray(eng.pos)[:, None], eng._tables_dev(),
        jnp.max(jnp.asarray(eng.pos)) // eng.block_size + 1,
        jnp.asarray(eng.active)[:, None])
    return np.asarray(logits[:, 0])


@pytest.mark.parametrize("prompt_len,held", [(29, (0, 16)), (33, (4, 8)),
                                             (6, (0, 16)), (17, (14, 16))])
def test_chunked_prefill_then_decode_matches_the_references_full_forward(
        prompt_len, held):
    """Window 8, block 4, chunk 8, contexts of 40: blocks are freed in the
    middle of the prompt and in the middle of decode. Logits compared."""
    cfg = bench_cfg(held=held)
    eng = _engine(cfg)
    assert eng.window == 8 and eng._kinded
    win = eng._kv.kinds["window"]
    ids = list(np.random.default_rng(prompt_len).integers(0, 96, prompt_len))
    n_new = 40 - prompt_len
    assert eng.begin_request(1, np.asarray(ids, np.int32), n_new)
    freed0 = sc._M_window_freed.value()
    first = None
    while first is None:
        first = eng.prefill_chunk(1)
        eng._kv.check_invariants()
        assert win.used_blocks() <= win.hold_blocks
    seq = ids + [first]
    worst = 0.0
    for _ in range(n_new - 1):
        # one more token: the table moves first, as step() does
        eng._extend_tables()
        got = _step_logits(eng)[1]
        ref = reference_logits(cfg, np.asarray(seq, np.int32))
        assert int(np.argmax(ref[-1])) == int(np.argmax(got))
        worst = max(worst, float(np.abs(got - ref[-1]).max()))
        seq.append(int(eng.step()[1]))
        assert seq[-1] == int(np.argmax(ref[-1]))
        assert win.used_blocks() <= win.hold_blocks
    ref = reference_logits(cfg, np.asarray(seq[:-1], np.int32))
    assert first == int(np.argmax(ref[prompt_len - 1]))
    assert worst < 5e-5
    if prompt_len > 16:
        assert sc._M_window_freed.value() > freed0        # freed mid-prompt
    # a window layer's table holds the tail only, the full layer's all of it
    assert (eng._kv.kinds["window"].block_tables[1] >= 0).sum() <= 3
    assert (eng._kv.kinds["full"].block_tables[1] >= 0).sum() == 10
    eng.release(1)
    eng._kv.check_invariants()
    for c in eng._kv.kinds.values():
        assert c.used_blocks() == 0 and c.stats()["blocks_reserved"] == 0


def test_the_server_serves_it_through_submit_and_the_spans_carry_its_counts():
    cfg = bench_cfg(held=(0, 8))
    eng = _engine(cfg, max_slots=4)
    srv = GenerationServer(eng)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (5, 40, 17, 33)]
    reqs = [srv.submit(p, 9) for p in prompts]
    for r in reqs:
        assert r["done"].wait(300) and r["error"] is None
    for p, r in zip(prompts, reqs):
        seq = list(p)
        for tok in r["out"]:
            ref = reference_logits(cfg, np.asarray(seq, np.int32))
            assert tok == int(np.argmax(ref[-1]))
            seq.append(tok)
    assert set(eng.last_aux) == {"moe_rows", "moe_experts_hit",
                                 "moe_max_rows", "moe_launches"}
    eng.active[:2] = True
    eng.pos[:2] = (20, 3)
    counts = srv._launch_counts()
    assert counts["live_tokens"] == 21 + 4 and counts["window_tokens"] == 8 + 4
    eng.active[:] = False
    assert eng._chunk_counts(16, 8, 8)["window_tokens"] == 15
    assert eng._chunk_counts(0, 8, 8)["window_tokens"] == 8
    assert srv.shutdown(drain=True, timeout=60)
    kinds = srv.stats()["kv_pool"]["kinds"]
    assert set(kinds) == {"full", "window"}
    assert all(k["blocks_used"] == 0 for k in kinds.values())
    assert sc._G_kind_blocks.value(kind="window") == 0


# -- the allocator -------------------------------------------------------------

def _kinded(full=40, window=12):
    return sc.KindedKVCache(3, 64, 4, {
        "full": {"num_blocks": full},
        "window": {"num_blocks": window, "window": 8, "window_slack": 8}})


def test_admission_reserves_by_kind():
    kv = _kinded()
    win, full = kv.kinds["window"], kv.kinds["full"]
    assert win.hold_blocks == 5 and full.hold_blocks == 16
    assert kv.admit(0, 30, 44)
    # the full table takes the whole need, the window table its hold only
    assert full.used_blocks() == 8 and full.stats()["blocks_reserved"] == 3
    assert win.used_blocks() + win.stats()["blocks_reserved"] == 5
    assert kv.admit(1, 6, 10)
    assert win.used_blocks() + win.stats()["blocks_reserved"] == 5 + 3
    # the window pool cannot hold a third: nothing is taken from either kind
    before = full.stats()
    assert not kv.admit(2, 30, 44)
    assert full.stats() == before
    kv.check_invariants()
    with pytest.raises(ValueError):
        _kinded(full=4).admit(0, 30, 44)          # could never fit


def test_a_window_table_frees_behind_the_window_and_never_passes_its_hold():
    kv = sc.PagedKVCache(2, 64, 4, 12, window=8, window_slack=4, kind="window")
    assert kv.admit(0, 30, 40)
    held = []
    for start in range(0, 30, 4):
        kv.advance(0, start, min(start + 3, 29))
        kv.check_invariants()
        live = np.flatnonzero(kv.block_tables[0] >= 0)
        # every position the chunk's rows can see is mapped
        assert live.min() <= max(start - 7, 0) // 4 and live.max() >= \
            min(start + 3, 29) // 4
        held.append(len(live) + kv.stats()["blocks_reserved"])
    assert max(held) <= kv.hold_blocks
    for pos in range(30, 40):
        kv.ensure_token(0, pos)
    assert np.flatnonzero(kv.block_tables[0] >= 0).tolist() == [8, 9]
    # nothing is reserved past the request's last block
    assert kv.stats()["blocks_reserved"] == 0
    assert kv.release(0) == 2
    assert kv.stats()["blocks_free"] == 12
    kv.check_invariants()


def test_release_returns_every_kind_and_counts_evictions():
    kv = _kinded()
    assert kv.admit(0, 20, 30)
    kv.advance(0, 0, 7)
    before = sc._M_evictions.value()
    assert kv.release(0, evicted=True) > 0
    assert sc._M_evictions.value() > before
    assert kv.used_blocks() == 0 and kv.occupied_slots() == 0
    assert kv.stats()["kinds"]["window"]["evictions"] > 0
    kv.check_invariants()


def test_a_launch_that_writes_more_rows_than_the_slack_is_an_error():
    kv = sc.PagedKVCache(1, 64, 4, 12, window=8, window_slack=4, kind="window")
    assert kv.admit(0, 40, 48)
    with pytest.raises(RuntimeError, match="window_slack"):
        kv.advance(0, 0, 31)


def test_a_window_table_is_not_rolled_back_or_mapped_far_ahead():
    kv = sc.PagedKVCache(1, 64, 4, 12, window=8, window_slack=4, kind="window")
    assert kv.admit(0, 10, 20)
    with pytest.raises(NotImplementedError):
        kv.truncate(0, 4)
    with pytest.raises(NotImplementedError):
        kv.reserve_through(0, 19)


# -- the kernel's lower bound ----------------------------------------------------

@pytest.mark.parametrize("T,window,dtype", [(1, 9, "float32"), (4, 9, "float32"),
                                            (4, 3, "float32"), (8, 16, "bfloat16"),
                                            (1, 64, "float32")])
def test_the_kernels_lower_bound_matches_the_jnp_walk(T, window, dtype):
    rng = np.random.default_rng(T * 100 + window)
    S, K, Rp, D, bs, NB, MB = 3, 2, 4, 16, 4, 48, 12
    q = jnp.asarray(rng.normal(size=(S, T, K * Rp, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(NB, bs, K * D)), dtype)
    vp = jnp.asarray(rng.normal(size=(NB, bs, K * D)), dtype)
    tables = rng.permutation(NB)[:S * MB].reshape(S, MB).astype(np.int32)
    last = np.asarray([40, 9, 27])
    pos = jnp.asarray(last[:, None] + np.arange(T)[None, :], jnp.int32)
    lower = pos - (window - 1)
    # blocks wholly behind every row's window are gone, as the table frees them
    for s in range(S):
        tables[s, :max(int(lower[s].min()), 0) // bs] = -1
    kp = kp.at[0].set(jnp.nan)     # what an unmapped entry clamps to
    vp = vp.at[0].set(jnp.nan)
    tables = jnp.asarray(np.where(tables == 0, 47, tables))
    walk = sc.paged_attention(q, kp, vp, tables, pos, block_size=bs, n_rep=Rp,
                              use_kernel=False, lower=lower)
    kern = pk.paged_attention_kernel(q, kp, vp, tables, pos, block_size=bs,
                                     n_rep=Rp, lower=lower, interpret=True)
    tol = 2e-2 if dtype == "bfloat16" else 2e-6
    assert np.isfinite(np.asarray(walk, np.float32)).all()
    assert np.abs(np.asarray(walk, np.float32)
                  - np.asarray(kern, np.float32)).max() < tol
    # and the bound does what it says: a dense softmax over the visible keys
    s, t = 0, T - 1
    lo, hi = max(int(lower[s, t]), 0), int(pos[s, t])
    keys = np.asarray(kp, np.float32)[np.asarray(tables[s])].reshape(-1, K, D)
    vals = np.asarray(vp, np.float32)[np.asarray(tables[s])].reshape(-1, K, D)
    sco = keys[lo:hi + 1, 0] @ np.asarray(q, np.float32)[s, t, 0] / np.sqrt(D)
    p = np.exp(sco - sco.max())
    dense = (p / p.sum()) @ vals[lo:hi + 1, 0]
    assert np.abs(np.asarray(walk, np.float32)[s, t, 0] - dense).max() < 10 * tol


def test_a_wide_chunk_reaches_the_kernel_a_tile_of_rows_at_a_time():
    assert sc._kernel_row_tile(64, 32, 128) == 64       # Yi's chunk: whole
    assert sc._kernel_row_tile(512, 128, 128) == 64     # Command A+: 8 tiles
    assert sc._kernel_row_tile(1, 128, 128) == 1
    rng = np.random.default_rng(5)
    S, T, K, Rp, D, bs, NB, MB = 1, 16, 2, 2, 16, 4, 32, 8
    q = jnp.asarray(rng.normal(size=(S, T, K * Rp, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, K * D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:MB][None], jnp.int32)
    pos = jnp.asarray(8 + np.arange(T)[None], jnp.int32)
    walk = sc.paged_attention(q, kp, kp, tables, pos, block_size=bs, n_rep=Rp,
                              use_kernel=False, lower=pos - 8)

    def rows(a):
        return a.reshape((4, 4) + a.shape[2:])
    tiled = pk.paged_attention_kernel(
        rows(q), kp, kp, jnp.repeat(tables, 4, axis=0), rows(pos),
        block_size=bs, n_rep=Rp, lower=rows(pos - 8), interpret=True)
    assert np.abs(np.asarray(walk) - np.asarray(tiled).reshape(walk.shape)).max() \
        < 2e-6


# -- the expert layer ------------------------------------------------------------

@pytest.mark.parametrize("held,block_t", [((0, 16), 16), ((4, 8), 16),
                                          ((12, 16), 8), ((0, 2), 32)])
def test_held_experts_are_dropless_and_leave_absent_experts_out(held, block_t):
    rng = np.random.default_rng(1)
    T, H, I, E, K = 24, 128, 256, 16, 4
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(E, H)) * 0.1, jnp.float32)
    gu = jnp.asarray(rng.normal(size=(E, H, 2 * I)) * 0.05, jnp.float32)
    dn = jnp.asarray(rng.normal(size=(E, I, H)) * 0.05, jnp.float32)
    idx, w = moe_share.sigmoid_topk_route(x, wr, K)
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    lo, hi = held
    want, per = np.zeros((T, H)), np.zeros(E, int)
    for t in range(T):
        for j in range(K):
            e = int(idx[t, j])
            if lo <= e < hi:
                g = np.asarray(x[t]) @ np.asarray(gu[e])
                a = g[:I] / (1 + np.exp(-g[:I])) * g[I:]
                want[t] += float(w[t, j]) * (a @ np.asarray(dn[e]))
                per[e] += 1
    for kw in ({}, {"interpret": True}):
        y, st = moe_share.held_experts_forward(x, idx, w, gu[lo:hi], dn[lo:hi],
                                               held, block_t, **kw)
        assert np.abs(np.asarray(y) - want).max() < 1e-5
        assert np.asarray(st).tolist() == [per.sum(), (per > 0).sum(), per.max()]


def test_the_router_is_float32_whatever_the_activations_are():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16)
    wr = jnp.asarray(rng.normal(size=(16, 32)), jnp.bfloat16)
    idx, w = moe_share.sigmoid_topk_route(x, wr, 4)
    assert w.dtype == jnp.float32
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(wr, np.float64).T)))
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.argsort(-s, -1)[:, :4], -1)).all()


def test_expert_rows_matmul_skips_dead_tiles_and_counts_its_path():
    from paddle_tpu.observability import metrics as om
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(3, 128, 256)), jnp.float32)
    ids = jnp.asarray([0, 0, 2, 2, 2, 2, 2, 2], jnp.int32)
    c = om.default_registry().get("pallas.path_selected_total")
    before = c.value(kernel="expert_rows_matmul", path="reference")
    for n_live in (3, 0, 8):
        want = np.zeros((64, 256))
        for t in range(n_live):
            want[8 * t:8 * t + 8] = np.asarray(lhs[8 * t:8 * t + 8]) @ \
                np.asarray(rhs[int(ids[t])])
        ref = gm.expert_rows_matmul(lhs, rhs, ids, jnp.int32(n_live), 8)
        ker = gm.expert_rows_matmul(lhs, rhs, ids, jnp.int32(n_live), 8,
                                    interpret=True)
        assert np.abs(np.asarray(ref) - want).max() < 1e-3
        assert np.abs(np.asarray(ker) - want).max() < 1e-3
    assert c.value(kernel="expert_rows_matmul", path="reference") == before + 3
    assert [moe_share.row_tile(t, 8, 128) for t in (8, 32, 512, 4096)] \
        == [16, 16, 64, 128]


# -- what is refused ---------------------------------------------------------------

def test_prefix_sharing_speculation_and_int8_are_refused_for_this_model():
    cfg = bench_cfg()
    model = seeded_model(cfg)
    with pytest.raises(ValueError, match="prefix sharing"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64, block_size=4,
                               prefill_chunk=8, prefix_cache=True)
    with pytest.raises(ValueError, match="prefix sharing"):
        sc.PagedKVCache(2, 64, 4, 12, window=8, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="int8"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64, int8=True)
    with pytest.raises(NotImplementedError, match="int8"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64, block_size=4,
                               prefill_chunk=8, kv_quant="int8")
    eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64, block_size=4,
                                 prefill_chunk=8)
    assert eng._kv.prefix_enabled is False           # off, never silently wrong
    with pytest.raises(NotImplementedError, match="speculative"):
        eng.make_draft(model)
    with pytest.raises(NotImplementedError, match="speculative"):
        eng.attach_draft(eng)
    with pytest.raises(NotImplementedError):
        eng.decode_steps(2)
    with pytest.raises(ValueError, match="experts_held"):
        Cohere2MoeForCausalLM(Cohere2MoeConfig.tiny(), experts_held=(4, 40))


def test_llama_is_the_first_user_of_the_seam_with_its_programs_names():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaServe
    paddle.seed(0)
    eng = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                 max_slots=2, max_seq=64)
    assert isinstance(eng._m, LlamaServe) and not eng._kinded
    assert eng.window is None and isinstance(eng._kv, sc.PagedKVCache)
    assert [sp["kind"] for sp in eng.cache_spec] == ["full", "full"]
    assert "window_tokens" not in eng._chunk_counts(0, 8, 8)
    out = eng.generate(np.arange(1, 12, dtype=np.int32), max_new_tokens=4)
    assert len(out) == 4 and eng.last_aux == {}
    assert eng._decode.__name__ == "serving.decode"
    assert [p.__name__ for p in eng._prefills.values()] == ["serving.prefill_b16"]
