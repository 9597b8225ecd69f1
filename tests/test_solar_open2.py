"""Solar Open 2 (`solar_open2`) through the paged serving engine, at tiny sizes
on the CPU with seeded weights: the chunk form of the gated delta rule against
the token-by-token recurrence, both kernels through the Pallas interpreter, the
model against the benchmark's plain reference, chunked prefill and decode
through the state pool against the reference's full forward (logits and
states), a slot reused by a second request (also under an overrun launch in
flight), padding and inactive slots, `reset_state`, the refusals, the share of
the experts against the uncut layer, the cache spec's state, and the counts the
spans and a launch carry."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM, SolarOpen2Config,
                               SolarOpen2ForCausalLM)
from paddle_tpu.models import glm_moe_dsa as gm
from paddle_tpu.models import solar_open2 as so
from paddle_tpu.observability import flight
from paddle_tpu.observability import metrics as om
from paddle_tpu.ops.pallas import kda
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine

from benchmark.lib import reference_solar_open2 as R
from benchmark.lib import weights_solar_open2 as W
from benchmark.runners import serve_paged_kda as runner

VOCAB = 96


def bench_cfg(held=(0, 16), **kw):
    """A configuration file's dict at a tiny size: 16 published experts, two
    periods of (GQA, KDA, KDA, KDA)."""
    cfg = dict(hidden_size=32, moe_intermediate_size=24, num_hidden_layers=8,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               gqa_layers=[0, 4], first_k_dense_replace=0, use_rope=False,
               use_gqa_gate=True, kda_use_full_proj=False,
               kda_allow_neg_eigval=True, gate_low_rank=8,
               linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                                   "num_heads": 2, "num_kv_heads": None},
               n_routed_experts=held[1] - held[0], experts_held_from=held[0],
               n_routed_experts_published=16, n_shared_experts=1,
               num_experts_per_tok=4, norm_topk_prob=True,
               routed_scaling_factor=1, rms_norm_eps=1e-5, vocab_size=VOCAB,
               max_position_embeddings=4096, tie_word_embeddings=False,
               dtype="float32")
    cfg.update(kw)
    return cfg


def seeded_model(cfg, seed=7):
    return runner.build_model(cfg, W.seed_u32(seed), "float32")


_REF = {}


def reference(cfg, ids, seed=7, pad_to=64):
    """The reference's (logits at every position of `ids`, {layer: KDA state
    after the last of them}). Logits come from a padded run (a causal model's
    earlier positions never see the padding), states from an exact one."""
    key = (cfg["experts_held_from"], cfg["n_routed_experts"], seed)
    if key not in _REF:
        s = W.seed_u32(seed)
        layers = [W.make_layer(cfg, jnp.float32)(s, i)
                  for i in range(cfg["num_hidden_layers"])]
        embed, norm, head = W.make_ends(cfg, jnp.float32)(s)
        _REF[key] = jax.jit(lambda x: R.forward_logits(
            cfg, layers, embed, norm, head, x, W.experts_held(cfg)))
    padded = np.zeros(max(pad_to, len(ids)), np.int32)
    padded[:len(ids)] = ids
    logits = np.asarray(_REF[key](jnp.asarray(padded))[0])[:len(ids)]
    states = {li: np.asarray(s) for li, s in _REF[key](
        jnp.asarray(np.asarray(ids, np.int32)))[1].items()}
    return logits, states


def _close(got, ref, rel=2e-5):
    return np.abs(np.asarray(got) - ref).max() < rel * max(ref.std(), 1e-3) + 1e-5


# -- the rule: chunk form, step form, kernels -------------------------------------

def _rows(T, H, dk, dv, seed, rate=1.0, beta=(0.0, 2.0)):
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.normal(size=(T, H, dk))) * dk ** -0.5
    k = unit(r.normal(size=(T, H, dk)))
    v = r.normal(size=(T, H, dv))
    g = -r.uniform(1e-3, 1.0, size=(T, H, dk)) * rate
    b = r.uniform(*beta, size=(T, H))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b)]


@pytest.mark.parametrize("T", [8, 64, 200])
@pytest.mark.parametrize("case", ["mixed", "decay_near_0", "decay_near_1",
                                  "beta_near_2"])
def test_the_chunk_form_is_the_token_by_token_recurrence(T, case):
    """Sub-chunks of 64 in WY form against `S = (I - b k k^T) Diag(a) S + b k
    v^T` a token: a decay of e^-40 a token (where `exp(-sum g)` overflows after
    three tokens), one of 1 - 1e-4, and `beta` at its upper end, where the
    eigenvalue along `k` is -1."""
    rate = {"decay_near_0": 40.0, "decay_near_1": 1e-4}.get(case, 1.0)
    beta = (1.9, 2.0) if case in ("beta_near_2", "decay_near_1") else (0.0, 2.0)
    q, k, v, g, b = _rows(T, 2, 16, 16, T, rate, beta)
    pool = jnp.asarray(np.random.default_rng(1).normal(size=(3, 2, 16, 16)),
                       jnp.float32)
    for fresh in (False, True):
        S0 = jnp.where(fresh, 0.0, pool[1])
        o_ref, S_ref = kda.kda_recurrence(S0, q, k, v, g, b)
        o, out = kda.kda_chunk(pool, 1, fresh, q, k, v, g, b)
        scale = max(float(jnp.abs(S_ref).max()), 1.0)
        assert float(jnp.abs(out[1] - S_ref).max()) < 2e-5 * scale
        assert float(jnp.abs(o - o_ref).max()) < 2e-5 * scale
        assert bool((out[0] == pool[0]).all() and (out[2] == pool[2]).all())


def test_padding_rows_of_a_chunk_leave_the_state_bit_for_bit():
    q, k, v, g, b = _rows(64, 2, 16, 16, 3)
    pool = jnp.asarray(np.random.default_rng(1).normal(size=(2, 2, 16, 16)),
                       jnp.float32)
    # a chunk of nothing but padding (g = 0, beta = 0)
    _, out = kda.kda_chunk(pool, 0, False, q, k, v, jnp.zeros_like(g),
                           jnp.zeros_like(b))
    assert np.asarray(out).tobytes() == np.asarray(pool).tobytes()
    # 20 rows and 44 of padding are the 20 rows
    live = (jnp.arange(64) < 20)
    _, padded = kda.kda_chunk(pool, 0, False, q, k, v,
                              jnp.where(live[:, None, None], g, 0.0),
                              jnp.where(live[:, None], b, 0.0))
    _, exact = kda.kda_recurrence(pool[0], q[:20], k[:20], v[:20], g[:20], b[:20])
    assert float(jnp.abs(padded[0] - exact).max()) < 1e-5


@pytest.mark.parametrize("act", [[True] * 5, [False, True, False, True, True],
                                 [False] * 5])
@pytest.mark.parametrize("interpret", [False, True])
def test_the_step_form_updates_the_active_slots_and_no_other(act, interpret):
    """The jnp form, and the kernel through the Pallas interpreter (which walks
    the active slots first and then stays on the last block it wrote)."""
    H, d = (16, 128) if interpret else (2, 16)
    assert kda.kernel_available(H, d, d) == interpret
    q, k, v, g, b = _rows(5, H, d, d, 11)
    pool = jnp.asarray(np.random.default_rng(2).normal(size=(5, H, d, d)),
                       jnp.float32)
    o, out = kda.kda_step(pool, q, k, v, g, b, jnp.asarray(act),
                          interpret=interpret)
    for s in range(5):
        if act[s]:
            o_ref, S_ref = kda.kda_recurrence(pool[s], q[s:s + 1], k[s:s + 1],
                                              v[s:s + 1], g[s:s + 1], b[s:s + 1])
            assert float(jnp.abs(out[s] - S_ref).max()) < 1e-5
            assert float(jnp.abs(o[s] - o_ref[0]).max()) < 1e-5
        else:
            assert np.asarray(out[s]).tobytes() == np.asarray(pool[s]).tobytes()
            assert not np.asarray(o[s]).any()


@pytest.mark.parametrize("T,fresh", [(128, False), (40, True)])
def test_the_chunk_kernel_matches_its_jnp_form(T, fresh):
    q, k, v, g, b = _rows(T, 8, 128, 128, T, 3.0)
    pool = jnp.asarray(np.random.default_rng(2).normal(size=(3, 8, 128, 128)),
                       jnp.float32)
    o_ref, S_ref = kda.kda_chunk(pool, 2, fresh, q, k, v, g, b, use_kernel=False)
    o, S = kda.kda_chunk(pool, 2, fresh, q, k, v, g, b, interpret=True)
    assert float(jnp.abs(S - S_ref).max()) < 1e-5
    assert float(jnp.abs(o - o_ref).max()) < 1e-5
    assert np.asarray(S[:2]).tobytes() == np.asarray(pool[:2]).tobytes()


# -- the model against the reference ----------------------------------------------

@pytest.mark.parametrize("length", [5, 19, 40])
def test_model_forward_matches_the_plain_reference(length):
    cfg = bench_cfg()
    model = seeded_model(cfg)
    ids = np.random.default_rng(length).integers(0, VOCAB, length).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    assert _close(got, reference(cfg, ids)[0])


def test_loaded_weights_are_the_references_bit_for_bit():
    cfg = bench_cfg()
    params = dict(seeded_model(cfg).named_parameters())
    seed = W.seed_u32(7)
    for li in (0, 1):
        for name, leaf in W.make_layer(cfg, jnp.float32)(seed, li).items():
            got = params[runner.program_name(cfg, f"layers.{li}.{name}")]._data
            assert np.asarray(got).tobytes() == np.asarray(leaf).tobytes(), name
    lp = W.make_layer(cfg, jnp.float32)(seed, 1)
    # the decays of a layer are the grid's values whatever the seed: a token's
    # decay lies between 0.2 and 0.999
    other = W.make_layer(cfg, jnp.float32)(W.seed_u32(8), 1)
    for name in ("A_log", "dt_bias", "router_bias"):
        assert sorted(np.asarray(lp[name])) == sorted(np.asarray(other[name]))
        assert not np.array_equal(np.asarray(lp[name]), np.asarray(other[name])) \
            or name == "A_log"      # two heads may draw the same order
    rate = np.exp(np.asarray(lp["A_log"], np.float64))
    dt = np.log1p(np.exp(np.asarray(lp["dt_bias"], np.float64)))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert 1e-3 <= dt.min() and dt.max() <= 0.1 + 1e-6
    # every share of the router's bias holds the same values
    bias = np.asarray(lp["router_bias"]).reshape(-1, cfg["n_routed_experts"])
    assert all(sorted(row) == sorted(bias[0]) for row in bias)


# -- the engine: a state pool beside the block pool ---------------------------------

def _engine(cfg_kw=None, **kw):
    paddle.seed(5)
    model = seeded_model(bench_cfg(**(cfg_kw or {})))
    kw = dict(dict(max_slots=3, max_seq=96, block_size=4, prefill_chunk=8), **kw)
    return model, PagedLlamaDecodeEngine(model, **kw)


def _paged_logits(eng, slot, ids, chunk=8):
    """Logits at every position of `ids`: the prompt in chunks of `chunk` rows
    (the last one ragged) through `_forward_paged`, as the engine's chunk
    programs call it."""
    out, start = [], 0
    while start < len(ids):
        c = min(chunk, len(ids) - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :c] = ids[start:start + c]
        offs = jnp.arange(chunk)
        logits, eng.kvs, _, _ = eng._forward_paged(
            eng.params, eng.kvs, jnp.asarray(padded), (start + offs)[None, :],
            eng._tables_dev(slot)[None, :], None, (offs < c)[None, :],
            jnp.asarray([slot], jnp.int32))
        out.append(np.asarray(logits)[0, :c])
        start += c
    return np.concatenate(out)


def _states(eng, slot):
    return {li: np.asarray(p[slot]) for li, p in enumerate(eng.kvs["S"])
            if p is not None}


@pytest.mark.parametrize("n_prompt", [6, 27, 70])
def test_chunked_prefill_then_decode_match_the_references_full_forward(n_prompt):
    """Prompts of less than a chunk, of several chunks ending mid-chunk (the
    convolution's tail crosses every border) and of more than a sub-chunk; then
    5 decode steps: logits, not tokens, and every KDA layer's state. float32
    against float32: the tolerance is rounding's (2e-5 of the logits' spread, 1e-4
    of a state's norm: a state sums 75 rank-one updates)."""
    cfg = bench_cfg()
    _, eng = _engine()
    ids = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 5).astype(np.int32)
    slot = 1
    # what an earlier request left in the slot is read by nobody
    eng.kvs["S"] = [None if p is None else p + 1.0 for p in eng.kvs["S"]]
    eng.kvs["conv"] = [None if p is None else p + 1.0 for p in eng.kvs["conv"]]
    assert eng.begin_request(slot, ids[:n_prompt], 8)
    got = _paged_logits(eng, slot, ids[:n_prompt])
    ref, _ = reference(cfg, ids)
    assert _close(got, ref[:n_prompt])
    untouched = {li: np.asarray(p[0]) for li, p in enumerate(eng.kvs["S"])
                 if p is not None}
    for p in range(n_prompt, n_prompt + 5):
        eng._kv.ensure_token(slot, p)
        last = np.zeros((3, 1), np.int32)
        last[slot, 0] = ids[p]
        pos = np.zeros(3, np.int32)
        pos[slot] = p
        act = np.zeros(3, bool)
        act[slot] = True
        logits, eng.kvs, aux, _ = eng._forward_paged(
            eng.params, eng.kvs, jnp.asarray(last), jnp.asarray(pos)[:, None],
            eng._tables_dev(), None, jnp.asarray(act)[:, None])
        assert _close(np.asarray(logits)[slot, 0], ref[p])
    _, want = reference(cfg, ids)
    for li, state in _states(eng, slot).items():
        assert R.state_gap(state, want[li]) < 1e-4, li
    # the slots that were not active hold what they held, bit for bit
    for li, was in untouched.items():
        assert np.asarray(eng.kvs["S"][li][0]).tobytes() == was.tobytes()


def test_the_cache_spec_names_a_state_and_one_path_allocates_it():
    _, eng = _engine()
    kinds = [sp["kind"] for sp in eng.cache_spec]
    assert kinds == ["full", "state", "state", "state"] * 2
    assert all(sp["pools"] == {} and set(sp["state"]) == {"S", "conv"}
               for sp in eng.cache_spec if sp["kind"] == "state")
    # the GQA layers' cache is the plain one-table cache: no table a kind
    assert eng._stateful and not eng._kinded
    assert type(eng._kv).__name__ == "PagedKVCache"
    assert set(eng.kvs) == {"k", "v", "S", "conv"}
    assert [p is not None for p in eng.kvs["k"]] == [True, False, False, False] * 2
    assert [p is not None for p in eng.kvs["S"]] == [False, True, True, True] * 2
    assert eng.kvs["S"][1].shape == (3, 2, 16, 16)
    assert eng.kvs["S"][1].dtype == jnp.float32
    assert eng.kvs["conv"][1].shape == (3, 3 * 3 * 2 * 16)
    assert eng.head_dim == 16 and eng.n_rep == 2 and eng.window is None
    eng._kv.admit(0, 9, 12)
    assert eng.pool_blocks_in_use() == {"k": 2 * 3, "v": 2 * 3}
    bytes_ = 3 * 6 * (2 * 16 * 16 * 4 + 3 * 96 * 4)
    assert eng.state_stats() == {"state_slots": 3, "state_slots_in_use": 0,
                                 "state_bytes": bytes_}
    # a model all of whose layers are `full` has no state and no such stats
    paddle.seed(1)
    llama = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                   max_slots=2, max_seq=32, block_size=4)
    assert not llama._stateful and llama.state_stats() == {}
    assert set(llama.kvs) == {"k", "v"}


def test_reset_state_rebuilds_the_state_as_zeros():
    _, eng = _engine()
    ids = np.random.default_rng(2).integers(0, VOCAB, 13).astype(np.int32)
    eng.prefill(2, ids, budget=4)
    eng.step()
    assert all(np.asarray(s).any() for s in _states(eng, 2).values())
    assert eng.state_stats()["state_slots_in_use"] == 1
    eng.reset_state()
    assert eng.state_stats()["state_slots_in_use"] == 0
    for name in ("S", "conv"):
        assert all(p is None or not np.asarray(p).any() for p in eng.kvs[name])
    assert eng.pool_blocks_in_use() == {"k": 0, "v": 0}
    # and serves on, with the programs it had
    want = eng.generate(ids, max_new_tokens=4, slot=2)
    _, fresh = _engine()
    assert fresh.generate(ids, max_new_tokens=4, slot=0) == want


def test_what_the_model_does_not_support_is_refused():
    paddle.seed(5)
    model = seeded_model(bench_cfg())
    with pytest.raises(ValueError, match="prefix sharing is not supported"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="int8 projections"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32, int8=True)
    with pytest.raises(NotImplementedError, match="state layers"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32, kv_quant="int8")
    eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32)
    assert eng._kv.prefix_enabled is False
    with pytest.raises(NotImplementedError, match="keeps no history"):
        eng.make_draft(model, num_layers=1)
    with pytest.raises(NotImplementedError, match="keeps no history"):
        eng.attach_draft(eng)
    with pytest.raises(ValueError, match="not a range"):
        SolarOpen2ForCausalLM(SolarOpen2Config.tiny(), experts_held=(4, 12))
    # a window of tokens a slot (a verify window) is not a state layer's
    with pytest.raises(NotImplementedError, match="no window of tokens"):
        eng._forward_paged(eng.params, eng.kvs, jnp.zeros((2, 3), jnp.int32),
                           jnp.zeros((2, 3), jnp.int32), eng._tables_dev(), None,
                           jnp.ones((2, 3), bool))


# -- a slot reused ------------------------------------------------------------------

def _wait(reqs, timeout=300):
    for r in reqs:
        assert r["done"].wait(timeout), "request did not finish"
        assert r["error"] is None, r["error"]


def test_a_slot_reused_by_a_second_request_gives_what_a_fresh_engine_gives():
    """Directly: the second request's first chunk starts at position 0, so its
    program reads the state and the tail as zeros, whatever the first left."""
    _, eng = _engine(max_slots=1)
    rng = np.random.default_rng(9)
    a = rng.integers(0, VOCAB, 21).astype(np.int32)
    b = rng.integers(0, VOCAB, 13).astype(np.int32)
    resets0 = om.default_registry().get("serving.state_resets_total").value()
    eng.generate(a, max_new_tokens=6)
    left = _states(eng, 0)
    assert all(np.asarray(s).any() for s in left.values())
    got = eng.generate(b, max_new_tokens=6)
    _, fresh = _engine(max_slots=1)
    assert got == fresh.generate(b, max_new_tokens=6)
    for li, s in _states(eng, 0).items():
        assert np.asarray(s).tobytes() == _states(fresh, 0)[li].tobytes()
    # one reset a request, at its first chunk, with its slot in the flight ring
    assert om.default_registry().get(
        "serving.state_resets_total").value() - resets0 == 3
    assert [e["attrs"]["slot"] for e in flight.events(category="serving")
            if e["name"] == "state_reset"][-3:] == [0, 0, 0]


def test_a_slot_reused_while_the_first_requests_overrun_launch_is_in_flight():
    """Behind the server, one slot, an EOS in the first request: the loop has
    launch n+1 enqueued for it when the EOS is seen, releases the slot and
    admits the second request, whose first chunk is enqueued BEHIND that launch
    and reads zeros: the second stream is a fresh engine's."""
    model, eng = _engine(max_slots=1)
    _, oracle = _engine(max_slots=1)
    rng = np.random.default_rng(4)
    first = rng.integers(0, VOCAB, 19).astype(np.int32)
    second = rng.integers(0, VOCAB, 11).astype(np.int32)
    stream = oracle.generate(first, max_new_tokens=10)
    eos = stream[4]
    k = stream.index(eos)
    want = oracle.generate(second, max_new_tokens=8)
    assert eos not in want
    over = om.default_registry().get("serving.overrun_tokens_total")
    over0 = over.value()
    eng.eos_id = eos
    srv = GenerationServer(eng)
    try:
        r1, r2 = srv.submit(first, 10), srv.submit(second, 8)
        _wait([r1, r2])
        assert list(r1["out"]) == stream[:k + 1]
        assert list(r2["out"]) == want
        assert over.value() - over0 == 1        # the launch that was in flight
        assert srv.stats()["launched_ahead"] > 0
        pool = srv.stats()["kv_pool"]
        assert pool["state_slots"] == 1 and pool["state_bytes"] > 0
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    assert srv.stats()["kv_pool"]["state_slots_in_use"] == 0
    assert om.default_registry().get("serving.state_bytes").value() \
        == eng.state_stats()["state_bytes"]


def test_the_served_stream_is_the_models_own_greedy_stream():
    model, eng = _engine()
    ids = np.random.default_rng(3).integers(0, VOCAB, 21).astype(np.int32)
    want, seq = [], list(ids)
    for _ in range(6):
        logits = np.asarray(model(paddle.to_tensor(np.asarray(seq)[None]))._data)
        want.append(int(logits[0, -1].argmax()))
        seq.append(want[-1])
    srv = GenerationServer(eng)
    try:
        assert srv.generate(ids, max_new_tokens=6) == want
        assert set(eng.last_aux) == {"moe_rows", "moe_experts_hit",
                                     "moe_max_rows", "moe_launches"}
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    pool = srv.stats()["kv_pool"]
    assert pool["blocks_used"] == 0 and pool["blocks_reserved"] == 0


# -- the share of the experts ---------------------------------------------------------

def _sparse_layer_inputs(seed=3):
    cfg = bench_cfg()
    lp = W.make_layer(cfg, jnp.float32)(W.seed_u32(seed), 2)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(24, 32)), jnp.float32)
    return cfg, lp, x


def _share_of(lp, lo, hi):
    return dict(lp, experts_gate_up=lp["experts_gate_up"][lo:hi],
                experts_down=lp["experts_down"][lo:hi])


def test_the_shares_add_up_to_the_uncut_layer():
    """The 8 shares' routed parts, plus the shared expert counted once, are the
    reference's layer with every expert; and the counts a share hands back are
    the counts by hand."""
    cfg, lp, x = _sparse_layer_inputs()
    c = runner.model_config(cfg, "float32")
    shared = np.asarray(gm.swiglu(x, lp["shared_gate"], lp["shared_up"],
                                  lp["shared_down"]))
    total = shared.copy()
    rows = 0
    s = jax.nn.sigmoid(jnp.einsum("th,eh->te", x, lp["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    chosen = np.asarray(jax.lax.top_k(s + lp["router_bias"], 4)[1])
    for lo in range(0, 16, 2):
        m, counts = so.experts_block(c, _share_of(lp, lo, lo + 2), x, (lo, lo + 2))
        total += np.asarray(m) - shared
        rows += int(counts[0])
        by_hand = [(chosen == e).sum() for e in (lo, lo + 1)]
        assert list(np.asarray(counts)) == [
            sum(by_hand), sum(n > 0 for n in by_hand), max(by_hand)]
    assert rows == 24 * 4                      # every (row, choice) pair once
    f32 = {k: v.astype(jnp.float32) for k, v in lp.items()}
    whole = np.asarray(R.experts(f32, x, cfg, (0, 16), "f32")[0])
    routed = np.abs(whole - shared).max()
    assert routed > 0.2 * np.abs(whole).max()      # the routed part is not nothing
    assert np.abs(total - whole).max() < 1e-3 * routed


# -- what the spans and a launch carry --------------------------------------------------

def test_the_spans_count_the_states_and_a_launch_hands_back_the_expert_counts():
    model, eng = _engine()
    for tokens, want in [(5, 1), (64, 1), (65, 2), (512, 8)]:
        assert eng._chunk_counts(0, tokens, 512)["state_subchunks"] == want
    srv = GenerationServer(eng)
    try:
        eng.pos[:] = [3, 20, 11]
        eng.active[:] = [True, True, False]
        counts = srv._launch_counts()
        assert counts["rows"] == 2 and counts["state_slots"] == 2
        assert counts["live_tokens"] == 4 + 21 and "walk_tokens" in counts
        assert counts["state_bytes_moved"] == 2 * 2 * eng.state_slot_bytes
        eng.pos[:] = 0
        eng.active[:] = False
    finally:
        srv.shutdown(drain=False, timeout=30)
    # a decode launch's counts against counts by hand: the rows of all three
    # slots go through the experts (an inactive slot's row is computed and
    # dropped), 8 layers, 4 choices a row, all 16 experts held
    ids = np.random.default_rng(3).integers(0, VOCAB, 9).astype(np.int32)
    eng.prefill(1, ids, budget=4)
    _, counts = eng.step_collect(eng.step_enqueue())
    assert counts["moe_rows"] == 3 * 8 * 4 and counts["moe_launches"] == 1
    assert 8 <= counts["moe_experts_hit"] <= 8 * 12
    assert counts["moe_max_rows"] <= 3 * 8
    # a model with no state layer carries no such count
    paddle.seed(1)
    llama = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                   max_slots=2, max_seq=32, block_size=4)
    assert "state_subchunks" not in llama._chunk_counts(0, 5, 8)
