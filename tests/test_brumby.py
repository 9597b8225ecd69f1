"""Brumby (`brumby`) through the paged serving engine, at tiny sizes on the CPU
with seeded weights: the symmetric square's identity, the step and chunk forms
of power retention (jnp and the Pallas interpreter) against the token-by-token
recurrence and the quadratic form, the model against the benchmark's plain
reference, chunked prefill then decode through the state pool against the
reference's full forward (logits and states), the engine with no block pool
(no table, no allocator, admission by slot, prompts past any block count), a
slot reused, the refusals, and the counts the spans carry."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (BrumbyConfig, BrumbyForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.models import brumby as bm
from paddle_tpu.observability import metrics as om
from paddle_tpu.ops.pallas import power_retention as pr
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine

from benchmark.lib import reference_brumby as R
from benchmark.lib import weights_brumby as W
from benchmark.runners import serve_paged_state as runner

VOCAB = 256


def bench_cfg(**kw):
    """A configuration file's dict at the tiny size: hidden 64, 4 query and 2
    KV heads of 16, 2 layers."""
    cfg = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=VOCAB, rms_norm_eps=1e-6, rope_theta=1e6,
               max_position_embeddings=4096, tie_word_embeddings=False,
               attention_bias=False, use_sliding_window=False,
               rope_scaling=None, hidden_act="silu", dtype="float32")
    cfg.update(kw)
    return cfg


def seeded_model(cfg=None, seed=7):
    return runner.build_model(cfg or bench_cfg(), W.seed_u32(seed), "float32")


_REF = {}


def reference(ids, seed=7):
    """The reference's (logits at every position of `ids`, {layer: (S, z)
    after the last of them})."""
    cfg = bench_cfg()
    if seed not in _REF:
        s = W.seed_u32(seed)
        layers = [W.make_layer(cfg, jnp.float32)(s, i)
                  for i in range(cfg["num_hidden_layers"])]
        _REF[seed] = (layers, W.make_ends(cfg, jnp.float32)(s))
    layers, ends = _REF[seed]
    logits, states = R.forward_logits(cfg, layers, *ends,
                                      jnp.asarray(np.asarray(ids, np.int32)))
    return np.asarray(logits), {li: tuple(np.asarray(x) for x in st)
                                for li, st in states.items()}


def _close(got, ref, rel=2e-5):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return np.abs(got - ref).max() <= rel * max(np.abs(ref).max(), 1e-30)


# -- the rule ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(d):
    rng = np.random.default_rng(d)
    q, k = rng.standard_normal((2, 7, d)).astype(np.float32)
    got = np.sum(np.asarray(pr.phi(q)) * np.asarray(pr.phi(k)), -1)
    want = np.sum(q.astype(np.float64) * k, -1) ** 2
    assert pr.feature_dim(d) == d * (d + 1) // 2 == pr.phi(q).shape[-1]
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # every unordered pair once, in the layout the reference names
    a, b, c = R.feature_pairs(d)
    assert len({(min(x, y), max(x, y)) for x, y in zip(a, b)}) == len(a)
    assert np.abs(np.asarray(pr.phi(q)) - q[:, a] * q[:, b] * c).max() < 1e-6
    assert np.abs(np.asarray(R.phi(jnp.asarray(q))) - q[:, a] * q[:, b] * c
                  ).max() < 1e-6


def test_the_decay_is_e_to_the_x_to_float32_rounding():
    """Near 0 the decay is a Taylor sum, elsewhere `exp`: both sides of the
    switch at -0.125 hold e^x to float32's rounding, and 0 gives 1 exactly."""
    x = np.concatenate([-np.logspace(-9, 1.9, 4000), [0.0, -0.125, -0.1249]])
    got = np.asarray(pr.decay(jnp.asarray(x, jnp.float32)), np.float64)
    want = np.exp(x.astype(np.float32).astype(np.float64))
    assert np.abs(got / want - 1).max() < 2.5e-7
    assert got[-3] == 1.0


def _rows(T, Hk, r, d, seed):
    """Rows as the model makes them: q and k of unit RMS, v, a decay a KV head
    a token between 0.92 and 0.9999 and, on one head, near 0.5."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt(np.mean(x * x, -1, keepdims=True))
    q = unit(rng.standard_normal((T, Hk * r, d)))
    k = unit(rng.standard_normal((T, Hk, d)))
    v = rng.standard_normal((T, Hk, d))
    logit = rng.uniform(2.5, 9.0, (T, Hk))
    logit[:, 0] = rng.uniform(-0.5, 0.5, T)
    g = -np.log1p(np.exp(-logit))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g))


# the jnp forms at the tiny width; the kernels through the interpreter there
# and at the served head width
@pytest.mark.parametrize("d,Hk,r,interpret", [
    (16, 2, 2, False), (16, 2, 5, False), (16, 2, 2, True), (16, 2, 5, True),
    (128, 2, 5, True)])
def test_chunks_then_steps_are_the_recurrence_and_the_quadratic_form(
        d, Hk, r, interpret):
    """A request of 3 chunks (the last ragged, padded as the engine pads a
    bucket) then 6 decode steps, in slot 2 of 4 whose other slots hold state
    that must stay bit for bit: the outputs against the quadratic form, the
    state against the token-by-token recurrence. A fresh first chunk reads
    zeros whatever the slot held. Float32 against float64-free float32: the
    tolerances are rounding's (2e-5 of the largest output, 1e-5 of the
    state's largest entry)."""
    T, NS, slot, C = 40, 4, 2, 16
    q, k, v, g = _rows(T + 6, Hk, r, d, seed=d + r)
    D = pr.feature_dim(d)
    rng = np.random.default_rng(1)
    S = jnp.asarray(rng.standard_normal((NS, Hk, D, d)), jnp.float32)
    z = jnp.asarray(np.abs(rng.standard_normal((NS, Hk, D))), jnp.float32)
    other = np.asarray(S[1]).tobytes()
    outs = []
    for start in range(0, T, C):
        n = min(C, T - start)
        pad = lambda x: jnp.pad(x[start:start + n],
                                ((0, C - n),) + ((0, 0),) * (x.ndim - 1))
        o, S, z = pr.retention_chunk(S, z, slot, start == 0, pad(q), pad(k),
                                     pad(v), pad(g), interpret=interpret)
        outs.append(o[:n])
    act = jnp.asarray(np.arange(NS) == slot)
    for t in range(T, T + 6):
        every = lambda x: jnp.broadcast_to(x[t][None], (NS,) + x.shape[1:])
        o, S, z = pr.retention_step(S, z, every(q), every(k), every(v),
                                    every(g), act, interpret=interpret)
        assert not np.asarray(o)[~np.asarray(act)].any()
        outs.append(o[slot][None])
    o = np.concatenate([np.asarray(x) for x in outs])
    assert _close(o, pr.retention_quadratic(q, k, v, g))
    zero = (jnp.zeros((Hk, D, d)), jnp.zeros((Hk, D)))
    o_rec, S_rec, z_rec = pr.retention_recurrence(*zero, q, k, v, g)
    assert _close(S[slot], S_rec, 1e-5) and _close(z[slot], z_rec, 1e-5)
    # the recurrence's last outputs, past its ill-conditioned first tokens
    assert _close(o[-6:], o_rec[-6:], 1e-4)
    assert np.asarray(S[1]).tobytes() == other


def test_a_chunks_padding_rows_leave_the_state_bit_for_bit():
    q, k, v, g = _rows(16, 2, 2, 16, seed=3)
    D = pr.feature_dim(16)
    S0, z0 = jnp.zeros((2, 2, D, 16)), jnp.zeros((2, 2, D))
    _, S1, z1 = pr.retention_chunk(S0, z0, 0, True, q[:9], k[:9], v[:9], g[:9])
    live = (jnp.arange(16) < 9)[:, None]
    _, S2, z2 = pr.retention_chunk(
        S0, z0, 0, True, q, jnp.where(live[..., None], k, 0),
        jnp.where(live[..., None], v, 0), jnp.where(live, g, 0.0))
    assert _close(S2, S1, 1e-6) and _close(z2, z1, 1e-6)
    assert not np.asarray(S2[1]).any()


def test_the_seams_count_the_path_they_took():
    c = om.default_registry().get("pallas.path_selected_total")
    before = dict(c.series()) if c is not None else {}
    q, k, v, g = _rows(8, 2, 2, 16, seed=4)
    D = pr.feature_dim(16)
    S, z = jnp.zeros((1, 2, D, 16)), jnp.zeros((1, 2, D))
    pr.retention_chunk(S, z, 0, True, q, k, v, g)
    pr.retention_step(S, z, q[:1], k[:1], v[:1], g[:1], jnp.ones(1, bool))
    after = dict(om.default_registry().get(
        "pallas.path_selected_total").series())
    for kernel in ("retention_chunk", "retention_step"):
        key = next(key for key in after if dict(key) == {
            "kernel": kernel, "path": "reference"})
        assert after[key] == before.get(key, 0) + 1


# -- the model ------------------------------------------------------------------------

@pytest.mark.parametrize("length", [5, 19, 40])
def test_model_forward_matches_the_plain_reference(length):
    model = seeded_model()
    ids = np.random.default_rng(length).integers(0, VOCAB, length)
    got = np.asarray(model(paddle.to_tensor(ids[None].astype(np.int32)))._data)
    assert _close(got[0], reference(ids)[0])


def test_loaded_weights_are_the_references_bit_for_bit():
    cfg = bench_cfg()
    model = seeded_model(cfg)
    params = dict(model.named_parameters())
    lp = W.make_layer(cfg, jnp.float32)(W.seed_u32(7), 1)
    for short, name in bm.LAYER_PARAMS.items():
        assert np.asarray(params[f"model.layers.1.{name}"]._data).tobytes() \
            == np.asarray(lp[short]).tobytes(), short
    # the seeded gate keeps a token for 10 .. 10,000 tokens: e^gamma 0.92-0.9999
    bias = np.sort(np.asarray(lp["g_bias"]))
    assert np.allclose(bias, np.sort(W.gate_bias_grid(2)))
    decay = 1.0 / (1.0 + np.exp(-bias))
    assert 0.9 < decay[0] < decay[-1] < 0.9999


# -- the engine: a state pool and no block pool -----------------------------------------

def _engine(**kw):
    paddle.seed(5)
    model = seeded_model()
    kw = dict(dict(max_slots=3, max_seq=1024, block_size=4, prefill_chunk=16),
              **kw)
    return model, PagedLlamaDecodeEngine(model, **kw)


@pytest.mark.parametrize("n_prompt", [6, 37, 70])
def test_chunked_prefill_then_decode_match_the_references_full_forward(n_prompt):
    """Prompts of less than a chunk and of several chunks ending mid-chunk, in a
    slot that held another request's state; then 5 decode launches: logits, not
    tokens, and every layer's state. Float32 against float32: the tolerance is
    rounding's (2e-5 of the logits' spread, 1e-4 of a state's norm)."""
    _, eng = _engine()
    ids = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 5).astype(np.int32)
    slot, C = 1, eng.prefill_chunk_len
    eng.kvs["S"] = [p + 1.0 for p in eng.kvs["S"]]      # read by nobody
    eng.kvs["z"] = [p + 1.0 for p in eng.kvs["z"]]
    ref, _ = reference(ids)
    assert eng.begin_request(slot, ids[:n_prompt], 8)
    got, start = [], 0
    while start < n_prompt:
        c = min(C, n_prompt - start)
        padded = np.zeros((1, C), np.int32)
        padded[0, :c] = ids[start:start + c]
        offs = jnp.arange(C)
        logits, eng.kvs, _, _ = eng._forward_paged(
            eng.params, eng.kvs, jnp.asarray(padded), (start + offs)[None, :],
            None, None, (offs < c)[None, :], jnp.asarray([slot], jnp.int32))
        got.append(np.asarray(logits)[0, :c])
        start += c
    assert _close(np.concatenate(got), ref[:n_prompt])
    untouched = np.asarray(eng.kvs["S"][0][0]).tobytes()
    for p in range(n_prompt, n_prompt + 5):
        last, pos, act = (np.zeros((3, 1), np.int32), np.zeros(3, np.int32),
                          np.zeros(3, bool))
        last[slot, 0], pos[slot], act[slot] = ids[p], p, True
        logits, eng.kvs, _, _ = eng._forward_paged(
            eng.params, eng.kvs, jnp.asarray(last), jnp.asarray(pos)[:, None],
            None, None, jnp.asarray(act)[:, None])
        assert _close(np.asarray(logits)[slot, 0], ref[p])
    _, want = reference(ids)
    for li in range(2):
        got_state = (np.asarray(eng.kvs["S"][li][slot]),
                     np.asarray(eng.kvs["z"][li][slot]))
        assert R.state_gap(got_state, want[li]) < 1e-4, li
    assert np.asarray(eng.kvs["S"][0][0]).tobytes() == untouched


def test_the_engine_builds_no_table_and_admits_by_slot():
    """Every layer a state: no block table, no allocator, nothing uploaded a
    launch; a prompt longer than any block count a table would have given is
    admitted as long as a slot is free and `max_seq` holds it."""
    _, eng = _engine(max_slots=2, max_seq=2048, num_blocks=8)
    assert eng._stateful and not eng._pooled and not eng._kinded
    assert type(eng._kv).__name__ == "SlotStates"
    assert eng.num_blocks == 0 and eng._kv.block_tables is None
    assert eng._tables_dev() is None and eng._tables_dev(0) is None
    assert set(eng.kvs) == {"S", "z"}
    assert eng.kvs["S"][0].shape == (2, 2, 136, 16)
    assert eng.kvs["z"][1].shape == (2, 2, 136)
    assert eng.cache_spec[0]["pools"] == {}
    per_slot = 2 * 2 * (136 * 16 + 136) * 4
    assert eng.state_slot_bytes == per_slot
    assert eng.state_stats() == {"state_slots": 2, "state_slots_in_use": 0,
                                 "state_bytes": 2 * per_slot}
    long = np.random.default_rng(0).integers(0, VOCAB, 300).astype(np.int32)
    assert 300 > 8 * eng.block_size
    assert eng.begin_request(0, long, 1500)
    assert eng.begin_request(1, long[:5], 2000)
    with pytest.raises(ValueError, match="already holds"):
        eng._kv.admit(0, 3, 3)
    with pytest.raises(ValueError, match="prompt length"):
        eng.begin_request(0, np.zeros(2048, np.int32), 1)
    assert eng.pool_blocks_in_use() == {}
    assert eng._kv.stats()["slots_held"] == 2
    eng.release(0)
    eng.release(1)
    assert eng._kv.stats() == {"num_blocks": 0, "blocks_used": 0,
                               "blocks_reserved": 0, "slots_held": 0,
                               "evictions": 0}
    # a model with a block pool builds the one table it always had
    paddle.seed(1)
    llama = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                   max_slots=2, max_seq=32, block_size=4)
    assert llama._pooled and type(llama._kv).__name__ == "PagedKVCache"
    assert llama._tables_dev().shape == (2, 8) and llama.num_blocks == 16


def test_a_long_request_is_served_past_any_block_count():
    model, eng = _engine(max_slots=1, max_seq=512, prefill_chunk=64)
    ids = np.random.default_rng(8).integers(0, VOCAB, 150).astype(np.int32)
    want, seq = [], list(ids)
    for _ in range(4):
        logits = np.asarray(model(paddle.to_tensor(np.asarray(
            seq, np.int32)[None]))._data)
        want.append(int(logits[0, -1].argmax()))
        seq.append(want[-1])
    srv = GenerationServer(eng)
    try:
        assert srv.generate(ids, max_new_tokens=4) == want
        pool = srv.stats()["kv_pool"]
        assert pool["num_blocks"] == 0 and pool["state_slots"] == 1
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    assert srv.stats()["kv_pool"]["state_slots_in_use"] == 0
    assert srv.stats()["kv_pool"]["slots_held"] == 0


def test_a_slot_reused_by_a_second_request_gives_what_a_fresh_engine_gives():
    _, eng = _engine(max_slots=1)
    rng = np.random.default_rng(9)
    a = rng.integers(0, VOCAB, 21).astype(np.int32)
    b = rng.integers(0, VOCAB, 35).astype(np.int32)
    eng.generate(a, max_new_tokens=6)
    got = eng.generate(b, max_new_tokens=6)
    _, fresh = _engine(max_slots=1)
    assert got == fresh.generate(b, max_new_tokens=6)
    for name in ("S", "z"):
        for li in range(2):
            assert np.asarray(eng.kvs[name][li][0]).tobytes() \
                == np.asarray(fresh.kvs[name][li][0]).tobytes()
    # the device-resident decode window needs no table either
    eng.prefill(0, b, budget=8)
    toks = eng.decode_steps(3)
    fresh.prefill(0, b, budget=8)
    assert [int(fresh.step()[0]) for _ in range(3)] == list(toks[0])


def test_reset_state_rebuilds_the_state_as_zeros():
    _, eng = _engine()
    ids = np.random.default_rng(2).integers(0, VOCAB, 13).astype(np.int32)
    eng.prefill(2, ids, budget=4)
    eng.step()
    assert np.asarray(eng.kvs["S"][1][2]).any()
    eng.reset_state()
    assert eng.state_stats()["state_slots_in_use"] == 0
    assert not any(np.asarray(p).any() for n in ("S", "z") for p in eng.kvs[n])
    assert eng._kv.stats()["slots_held"] == 0


def test_what_the_model_does_not_support_is_refused():
    model = seeded_model()
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
    with pytest.raises(NotImplementedError, match="no window of tokens"):
        eng._forward_paged(eng.params, eng.kvs, jnp.zeros((2, 3), jnp.int32),
                           jnp.zeros((2, 3), jnp.int32), None, None,
                           jnp.ones((2, 3), bool))
    with pytest.raises(ValueError, match="biased projections"):
        runner.model_config(bench_cfg(attention_bias=True), "float32")


def test_the_spans_count_the_state_a_launch_moves():
    """`serving.decode` carries `state_slots` and `state_bytes_moved` (every
    layer's state of each stepped slot, read and written); `serving.prefill`
    carries `state_subchunks` in the chunk kernel's row tile."""
    _, eng = _engine()
    assert eng._m.state_subchunk == pr.ROWS == 128
    for tokens, want in [(5, 1), (128, 1), (129, 2), (512, 4)]:
        assert eng._chunk_counts(0, tokens, 512)["state_subchunks"] == want
    srv = GenerationServer(eng)
    try:
        eng.pos[:] = [3, 20, 11]
        eng.active[:] = [True, True, False]
        counts = srv._launch_counts()
        assert counts["rows"] == 2 and counts["state_slots"] == 2
        assert counts["state_bytes_moved"] == 2 * 2 * eng.state_slot_bytes
        assert "walk_tokens" not in counts
        eng.pos[:] = 0
        eng.active[:] = False
    finally:
        srv.shutdown(drain=False, timeout=30)
