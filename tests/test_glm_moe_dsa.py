"""GLM-MoE-DSA (GLM-5.2) through the paged serving engine, at tiny sizes on the
CPU with seeded weights: the model against the benchmark's plain reference,
chunked prefill and decode through the latent and index pools against the
reference's full forward on both sides of `index_topk`, the selection rule, the
shared selection, absorbed against expanded attention, the biased router, the
share of the experts against the uncut layer, the two kernels through the Pallas
interpreter, the cache spec's pools, the refusals, and the counts the spans
carry."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import serving_cache as sc
from paddle_tpu.incubate import moe_share
from paddle_tpu.models import (Cohere2MoeConfig, Cohere2MoeForCausalLM,
                               GlmMoeDsaConfig, GlmMoeDsaForCausalLM,
                               LlamaConfig, LlamaForCausalLM)
from paddle_tpu.models import glm_moe_dsa as gm
from paddle_tpu.observability import metrics as om
from paddle_tpu.ops.pallas import sparse_latent as sl
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine

from benchmark.lib import reference_glm_moe_dsa as R
from benchmark.lib import weights_glm_moe_dsa as W
from benchmark.runners import serve_paged_dsa as runner

TOPK = 8


def bench_cfg(held=(0, 16), **kw):
    """A configuration file's dict at a tiny size: 16 published experts, a dense
    layer then one period of the indexer pattern."""
    cfg = dict(hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
               num_hidden_layers=5, num_attention_heads=4, q_lora_rank=16,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, index_n_heads=2, index_head_dim=8, index_topk=TOPK,
               indexer_types=["full", "shared", "shared", "shared", "full"],
               mlp_layer_types=["dense"] + ["sparse"] * 4,
               first_k_dense_replace=1, n_routed_experts=held[1] - held[0],
               experts_held_from=held[0], n_routed_experts_published=16,
               n_shared_experts=1, num_experts_per_tok=4, norm_topk_prob=True,
               routed_scaling_factor=2.5, rms_norm_eps=1e-5, vocab_size=96,
               rope_parameters={"rope_theta": 8000000, "rope_type": "default"},
               max_position_embeddings=4096, tie_word_embeddings=False,
               dtype="float32")
    cfg.update(kw)
    return cfg


def seeded_model(cfg, seed=7):
    return runner.build_model(cfg, W.seed_u32(seed), "float32")


_REF = {}


def reference_logits(cfg, ids, seed=7, pad_to=64):
    """The reference's logits at every position of `ids` (padded to one length:
    a causal model's earlier positions never see the padding)."""
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
    return np.asarray(_REF[key](jnp.asarray(padded)))[:len(ids)]


def _close(got, ref):
    return np.abs(np.asarray(got) - ref).max() < 2e-5 * max(ref.std(), 1e-3) + 1e-5


@pytest.mark.parametrize("length", [5, 19, 40])
def test_model_forward_matches_the_plain_reference(length):
    cfg = bench_cfg()
    ids = np.random.default_rng(length).integers(0, 96, length).astype(np.int32)
    got = seeded_model(cfg)(paddle.to_tensor(ids[None]))._data[0]
    ref = reference_logits(cfg, ids)
    assert _close(got, ref)
    assert ref.std() > 0.01        # seeded weights give logits that differ


def test_loaded_weights_are_the_references_bit_for_bit():
    cfg = bench_cfg(held=(4, 8))
    params = dict(seeded_model(cfg).named_parameters())
    s = W.seed_u32(7)
    for i in range(cfg["num_hidden_layers"]):
        for name, leaf in W.make_layer(cfg, jnp.float32)(s, i).items():
            mine = params[runner.program_name(cfg, f"layers.{i}.{name}")]._data
            assert np.array_equal(np.asarray(mine), np.asarray(leaf)), name
    assert params["model.layers.1.mlp.experts.down_proj"].shape[0] == 4
    assert params["model.layers.1.mlp.gate.weight"].shape[0] == 16
    bias = np.asarray(params["model.layers.1.mlp.gate.e_score_correction_bias"]._data)
    assert bias.std() > 0.01 and abs(bias.mean()) < 0.05      # drawn, centred
    assert "model.layers.1.self_attn.indexer.wk.weight" not in params   # shared
    assert "model.layers.4.self_attn.indexer.wk.weight" in params      # full
    assert "model.layers.0.mlp.gate_proj.weight" in params             # dense


# -- the selection rule --------------------------------------------------------

@pytest.mark.parametrize("n,k", [(300, 8), (256, 8), (1000, 64), (50, 8), (6, 8)])
def test_select_topk_is_the_k_largest_ties_to_the_lower_position(n, k):
    rng = np.random.default_rng(n)
    s = rng.standard_normal((3, 5, n)).astype(np.float32)
    s[0, 0, :min(40, n)] = 0.5                               # a run of ties
    s[1, 1, :] = np.where(rng.random(n) < 0.5, 0.0, -0.0)    # +0 and -0 are equal
    valid = np.arange(n)[None, None, :] <= rng.integers(0, n, (3, 5))[:, :, None]
    picked, count = jax.jit(lambda a, b: sc.select_topk(a, b, k))(
        jnp.asarray(s), jnp.asarray(valid))
    picked, count = np.asarray(picked), np.asarray(count)
    r_idx, r_ok, _, _ = R.select(jnp.asarray(s.reshape(15, n)),
                                 jnp.asarray(valid.reshape(15, n)), k)
    r_idx, r_ok = np.asarray(r_idx).reshape(3, 5, -1), np.asarray(r_ok).reshape(3, 5, -1)
    for a in range(3):
        for b in range(5):
            want = sorted(sorted(np.flatnonzero(valid[a, b]),
                                 key=lambda c: (-(s[a, b, c] + 0.0), c))[:k])
            assert count[a, b] == len(want)
            assert list(np.flatnonzero(picked[a, b])) == want
            # the reference's own rule picks the same set
            assert sorted(r_idx[a, b][r_ok[a, b]]) == want


# -- the engine: pools by the spec, prefill in chunks, decode -------------------

def _engine(cfg_kw=None, **kw):
    paddle.seed(5)
    model = seeded_model(bench_cfg(**(cfg_kw or {})))
    kw = dict(dict(max_slots=3, max_seq=64, block_size=4, prefill_chunk=8), **kw)
    return model, PagedLlamaDecodeEngine(model, **kw)


def _paged_logits(eng, slot, ids, chunk=8):
    """Logits at every position of `ids`: the prompt in chunks of `chunk` rows
    (the last one ragged) through `_forward_paged`, as the engine's programs
    call it."""
    out, start = [], 0
    while start < len(ids):
        c = min(chunk, len(ids) - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :c] = ids[start:start + c]
        offs = jnp.arange(chunk)
        logits, eng.kvs, _, _ = eng._forward_paged(
            eng.params, eng.kvs, jnp.asarray(padded), (start + offs)[None, :],
            eng._tables_dev(slot)[None, :], None, (offs < c)[None, :])
        out.append(np.asarray(logits)[0, :c])
        start += c
    return np.concatenate(out)


@pytest.mark.parametrize("n_prompt", [6, 27, 45])
def test_chunked_prefill_then_decode_match_the_references_full_forward(n_prompt):
    """Prompts shorter than `index_topk`, past it, and ending mid-block (blocks
    of 4); then 5 decode steps through the pools: logits, not tokens."""
    cfg = bench_cfg()
    _, eng = _engine()
    rng = np.random.default_rng(n_prompt)
    ids = rng.integers(0, 96, n_prompt + 5).astype(np.int32)
    slot = 1
    assert eng.begin_request(slot, ids[:n_prompt], 8)
    got = _paged_logits(eng, slot, ids[:n_prompt])
    ref = reference_logits(cfg, ids)
    assert _close(got, ref[:n_prompt])
    # decode: one row a slot at its own position, the other slots inactive
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
        # the indexer layers count: rows attended, rows a dense walk would
        assert list(np.asarray(aux)[3:]) == [2 * min(p + 1, TOPK), 2 * (p + 1)]


def test_the_served_stream_is_the_models_own_greedy_stream():
    model, eng = _engine()
    ids = np.random.default_rng(3).integers(0, 96, 21).astype(np.int32)
    want, seq = [], list(ids)
    for _ in range(6):
        logits = np.asarray(model(paddle.to_tensor(np.asarray(seq)[None]))._data)
        want.append(int(logits[0, -1].argmax()))
        seq.append(want[-1])
    srv = GenerationServer(eng)
    try:
        assert srv.generate(ids, max_new_tokens=6) == want
        assert set(eng.last_aux) == {"moe_rows", "moe_experts_hit", "moe_max_rows",
                                     "dsa_selected", "dsa_visible", "moe_launches"}
        assert 0 < eng.last_aux["dsa_selected"] <= eng.last_aux["dsa_visible"]
    finally:
        assert srv.shutdown(drain=True, timeout=60)
    pool = srv.stats()["kv_pool"]
    assert pool["blocks_used"] == 0 and pool["blocks_reserved"] == 0
    g = om.default_registry().get("serving.kv_blocks_in_use")
    pools = {dict(k).get("pool") for k in g.series()}
    assert {"latent", "index"} <= pools


def test_the_cache_spec_names_the_pools_and_one_path_allocates_them():
    _, eng = _engine()
    spec = eng.cache_spec
    assert [sorted(sp["pools"]) for sp in spec] == [
        ["index", "latent"], ["latent"], ["latent"], ["latent"],
        ["index", "latent"]]
    assert all(sp["kind"] == "full" and sp["window"] is None for sp in spec)
    assert set(eng.kvs) == {"latent", "index"}
    assert [p is not None for p in eng.kvs["index"]] == [True, False, False, False, True]
    # a row is [c_kv ; k_rope] padded to whole 128-lane rows; keys as they are
    assert eng.kvs["latent"][1].shape == (eng.num_blocks, 4, 128)
    assert eng.kvs["index"][0].shape == (eng.num_blocks, 4, 8)
    assert gm.GlmMoeDsaConfig().latent_width == 640
    assert eng.select_k == TOPK and eng.window is None and eng.head_dim is None
    assert eng.walk_group_tokens() is None and eng._pa_kernel is None
    eng._kv.admit(0, 9, 12)
    assert eng.pool_blocks_in_use() == {"latent": 5 * 3, "index": 2 * 3}
    eng.reset_state()
    assert eng.pool_blocks_in_use() == {"latent": 0, "index": 0}
    # the other models' specs are in the same form, through the same path
    paddle.seed(1)
    llama = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                   max_slots=2, max_seq=32, block_size=4)
    assert all(sp["pools"] == {"k": 2 * 16, "v": 2 * 16} for sp in llama.cache_spec)
    assert llama.kvs["k"][0].shape == (llama.num_blocks, 4, 32)
    cohere = PagedLlamaDecodeEngine(
        Cohere2MoeForCausalLM(Cohere2MoeConfig.tiny()), max_slots=2, max_seq=32,
        block_size=4, prefill_chunk=8)
    assert [sp["kind"] for sp in cohere.cache_spec] == ["window"] * 3 + ["full"]
    assert cohere.kvs["k"][3].shape == (cohere.num_blocks["full"], 4, 32)
    assert cohere.kvs["v"][0].shape == (cohere.num_blocks["window"], 4, 32)
    assert set(cohere.pool_blocks_in_use()) == {"k", "v"}


def test_what_the_model_does_not_support_is_refused():
    paddle.seed(5)
    model = seeded_model(bench_cfg())
    with pytest.raises(ValueError, match="prefix sharing is not supported"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32, prefix_cache=True)
    with pytest.raises(NotImplementedError, match="int8 projections"):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32, int8=True)
    with pytest.raises(NotImplementedError, match="scale a .token, head."):
        PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32, kv_quant="int8")
    eng = PagedLlamaDecodeEngine(model, max_slots=2, max_seq=32)
    assert eng._kv.prefix_enabled is False
    with pytest.raises(NotImplementedError, match="speculative decoding"):
        eng.make_draft(model, num_layers=1)
    with pytest.raises(ValueError, match="layer 0 has no indexer"):
        GlmMoeDsaConfig.tiny(indexer_types=("shared",) * 5)
    with pytest.raises(ValueError, match="not a range"):
        GlmMoeDsaForCausalLM(GlmMoeDsaConfig.tiny(), experts_held=(4, 12))


# -- shared selection, absorption, the router, the share ------------------------

def test_shared_layers_attend_the_set_of_the_full_layer_before_them():
    """Only layer 0's indexer weights are perturbed: its own selection moves and
    with it the output of the `shared` layer behind it, given the SAME rows and
    pools; a `shared` layer with nothing handed on cannot run."""
    _, eng = _engine()
    serve = eng._m
    ids = np.random.default_rng(2).integers(0, 96, 30).astype(np.int32)
    assert eng.begin_request(0, ids, 2)
    _paged_logits(eng, 0, ids)                    # fills the pools
    rows, pos = jnp.arange(22, 30), jnp.arange(22, 30)[None, :]
    h = jnp.take(eng.params["emb"], jnp.asarray(ids)[rows], axis=0)[None]
    tab = eng._tables_dev(0)[None, :]
    nothing = jnp.zeros((1, 8), bool)

    def through_two(lp0):
        kv0 = {k: v[0] for k, v in eng.kvs.items()}
        kv1 = {"latent": eng.kvs["latent"][1]}
        h1, _, _, carry = serve.layer(eng, 0, lp0, h, kv0, pos, tab, None,
                                      nothing, None)
        # the SAME input for the shared layer, whatever layer 0 made of h
        out, _, _, kept = serve.layer(eng, 1, eng.params["layers"][1], h, kv1,
                                      pos, tab, None, nothing, carry)
        assert kept is carry
        return np.asarray(out), np.asarray(carry)

    lp0 = eng.params["layers"][0]
    out_a, sel_a = through_two(lp0)
    out_b, sel_b = through_two(dict(lp0, index_w=-lp0["index_w"]))
    assert (sel_a.sum(-1) == TOPK).all() and (sel_b.sum(-1) == TOPK).all()
    assert (sel_a != sel_b).any()
    assert np.abs(out_a - out_b).max() > 1e-4
    with pytest.raises((TypeError, AttributeError)):
        serve.layer(eng, 1, eng.params["layers"][1], h,
                    {"latent": eng.kvs["latent"][1]}, pos, tab, None, nothing, None)


def test_absorbed_attention_over_selected_rows_equals_expanded_attention():
    cfg = GlmMoeDsaConfig.tiny()
    rng = np.random.default_rng(0)
    nh, nope, rope, rank, vd = 4, 8, 4, 16, 8
    bs, nb, n_ctx, K = 4, 12, 37, 8
    kv_b = jnp.asarray(rng.normal(size=(nh * (nope + vd), rank)), jnp.float32)
    w_k, w_v = gm.kv_b_split(cfg, kv_b)
    pool = jnp.asarray(rng.normal(size=(nb, bs, cfg.latent_width)), jnp.float32)
    pool = pool.at[..., rank + rope:].set(0.0)
    tables = jnp.asarray(rng.permutation(nb)[:10][None, :], jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(1, 3, nh, nope)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(1, 3, nh, rope)), jnp.float32)
    idx = np.stack([rng.permutation(n_ctx)[:K] for _ in range(3)])
    n_sel = [K, 5, 1]
    picked = np.zeros((1, 3, 10 * bs), bool)
    for t in range(3):
        picked[0, t, idx[t, :n_sel[t]]] = True
    pos = jnp.full((1, 3), n_ctx - 1, jnp.int32)
    scale = 1.0 / np.sqrt(nope + rope)
    pad = jnp.zeros((1, 3, nh, cfg.latent_width - rank - rope))
    q = jnp.concatenate([jnp.einsum("sthd,hdc->sthc", q_nope, w_k), q_rope, pad], -1)
    att = sc.paged_latent_attention(q, pool, tables, jnp.asarray(picked), pos,
                                    block_size=bs, rank=rank, scale=scale)
    got = np.asarray(jnp.einsum("sthc,hvc->sthv", att, w_v))
    # expanded: keys and values of the selected positions, a head at a time
    flat = np.asarray(pool)[np.asarray(tables)[0]].reshape(-1, cfg.latent_width)
    for t in range(3):
        rows = flat[idx[t, :n_sel[t]]]
        k_nope = np.einsum("kc,hdc->khd", rows[:, :rank], np.asarray(w_k))
        v = np.einsum("kc,hvc->khv", rows[:, :rank], np.asarray(w_v))
        s = (np.einsum("hd,khd->hk", np.asarray(q_nope)[0, t], k_nope)
             + np.asarray(q_rope)[0, t] @ rows[:, rank:rank + rope].T) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        assert np.abs(got[0, t] - np.einsum("hk,khv->hv", p, v)).max() < 1e-5


def test_the_biased_router_chooses_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 32)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=16) * 0.3, jnp.float32)
    idx, weight = moe_share.sigmoid_topk_route(x, w, 4, True, bias=bias, scale=2.5)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x) @ np.asarray(w).T)))
    want = np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    assert np.allclose(np.asarray(weight),
                       2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)
    plain, _ = moe_share.sigmoid_topk_route(x, w, 4, True)
    assert (np.sort(np.asarray(plain), -1) != np.sort(want, -1)).any()  # bias decides


def test_command_a_plus_routing_is_bit_for_bit_what_it_was():
    """`sigmoid_topk_route` without a bias and a scale, against the function as
    it stood before it took them."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(16, 32)) * 0.3, jnp.bfloat16)

    def before(x, w_router, top_k, normalize=True):
        scores = jax.nn.sigmoid(jnp.einsum(
            "th,eh->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))
        weight, idx = jax.lax.top_k(scores, int(top_k))
        if normalize:
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), weight

    for normalize in (True, False):
        a = jax.jit(lambda x, w: moe_share.sigmoid_topk_route(x, w, 4, normalize))(x, w)
        b = jax.jit(lambda x, w: before(x, w, 4, normalize))(x, w)
        assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
        assert np.asarray(a[1]).tobytes() == np.asarray(b[1]).tobytes()


def _sparse_layer_inputs(seed=3):
    cfg = bench_cfg()
    lp = W.make_layer(cfg, jnp.float32)(W.seed_u32(seed), 2)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(24, 32)), jnp.float32)
    return cfg, lp, x


def _share_of(lp, lo, hi):
    return dict(lp, experts_gate_up=lp["experts_gate_up"][lo:hi],
                experts_down=lp["experts_down"][lo:hi])


@pytest.mark.parametrize("share", range(4))
def test_a_share_of_the_experts_matches_the_reference_given_the_same(share):
    cfg, lp, x = _sparse_layer_inputs()
    lo, hi = 4 * share, 4 * share + 4
    m, counts = gm.experts_block(runner.model_config(cfg, "float32"),
                                 _share_of(lp, lo, hi), x, (lo, hi))
    f32 = {k: v.astype(jnp.float32) for k, v in _share_of(lp, lo, hi).items()}
    ref = np.asarray(R.experts(f32, x, cfg, (lo, hi), "f32")[0])
    assert np.abs(np.asarray(m) - ref).max() < 1e-3 * np.abs(ref).max()
    assert 0 < int(counts[0]) <= 24 * 4 and int(counts[1]) <= 4


def test_the_shares_add_up_to_the_uncut_layer():
    """The 8 shares' routed parts, plus the shared expert counted once, are the
    reference's layer with every expert."""
    cfg, lp, x = _sparse_layer_inputs()
    c = runner.model_config(cfg, "float32")
    shared = np.asarray(gm.swiglu(x, lp["shared_gate"], lp["shared_up"],
                                  lp["shared_down"]))
    total = shared.copy()
    rows = 0
    for lo in range(0, 16, 2):
        m, counts = gm.experts_block(c, _share_of(lp, lo, lo + 2), x, (lo, lo + 2))
        total += np.asarray(m) - shared
        rows += int(counts[0])
    assert rows == 24 * 4                      # every (row, choice) pair once
    f32 = {k: v.astype(jnp.float32) for k, v in lp.items()}
    whole = np.asarray(R.experts(f32, x, cfg, (0, 16), "f32")[0])
    routed = np.abs(whole - shared).max()
    assert routed > 0.2 * np.abs(whole).max()      # the routed part is not nothing
    assert np.abs(total - whole).max() < 1e-3 * routed


# -- the kernels, through the Pallas interpreter ---------------------------------

def _index_case(name):
    """(q, w, pool, tables, positions) of an index-score case: blocks of 64, 8
    index heads of 128, tables drawn at random from the pool (no two blocks of
    a slot need be neighbours), -1 past a slot's mapped blocks."""
    rng = np.random.default_rng(sum(map(ord, name)))
    bs, J, D, NB = 64, 8, 128, 96
    if name == "decode_32_slots":
        pos = np.concatenate([[0, 63, 64, 2047, 2048, 30000],
                              rng.integers(0, 3000, 26)])
        pos[-1] = 0
    elif name == "chunk_512":           # rows 3900..4411 over three key tiles
        pos = 3900 + np.arange(512)
    elif name == "rows_16":             # two row tiles a slot
        pos = np.sort(rng.integers(0, 5000, (2, 16)), axis=1)
    else:
        pos = np.asarray([5000, 100, 4095])
    pos = pos.reshape(len(pos), -1) if pos.ndim == 2 else (
        pos[None] if name == "chunk_512" else pos[:, None])
    S, T = pos.shape
    MB = 480                            # 30,720 positions, 15 key tiles
    tables = rng.integers(0, NB, (S, MB)).astype(np.int32)
    if name == "shuffled_table":        # a slot's blocks in falling order
        tables[:] = (NB - 1 - np.arange(MB) % NB)[None]
    mapped = np.maximum(pos.max(axis=1), 0) // bs + 1
    if name == "unmapped_past_mapped":  # 20 blocks mapped under a row at 5000
        mapped[0] = 20
    if name == "decode_32_slots":
        mapped[-1] = 0                  # a free slot: no live row
    tables[np.arange(MB)[None, :] >= mapped[:, None]] = -1
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f32(S, T, J, D), f32(S, T, J), f32(NB, bs, D), jnp.asarray(tables),
            jnp.asarray(pos, jnp.int32))


INDEX_CASES = ["decode_32_slots", "shuffled_table", "unmapped_past_mapped",
               "rows_16", "chunk_512"]


def _index_kernel_against_its_jnp_form(name, interpret):
    q, w, pool, tables, pos = _index_case(name)
    bs = pool.shape[1]
    got = np.asarray(sl.index_scores(q, w, pool, tables, pos, block_size=bs,
                                     interpret=interpret))
    tab, pos = np.asarray(tables), np.asarray(pos)
    S, T = pos.shape
    assert got.shape == (S, T, tab.shape[1] * bs)
    for s in range(S):
        want = np.asarray(sl.index_scores(
            q[s:s + 1], w[s:s + 1], pool, tables[s:s + 1], pos[s:s + 1],
            block_size=bs, use_kernel=False))[0]
        cols = np.arange(got.shape[-1])
        for t in range(T):
            ok = (cols <= pos[s, t]) & (tab[s, cols // bs] >= 0)
            if ok.any():
                assert np.abs(got[s, t, ok] - want[t, ok]).max() \
                    < 1e-3 * np.abs(want[t, ok]).max()
        # key tiles past the row tile's last position are not scored: zeros
        for t0 in range(0, T, min(T, 8)):
            last = max(pos[s, t0:t0 + 8].max(), 0) // sl.KEY_TILE
            assert (got[s, t0:t0 + 8, (last + 1) * sl.KEY_TILE:] == 0).all()


@pytest.mark.parametrize("name", INDEX_CASES)
def test_the_index_score_kernel_matches_its_jnp_form(name):
    """The kernel reads a slot's keys through its table row (interpreted) and
    scores every valid position as the jnp form does after its gather."""
    _index_kernel_against_its_jnp_form(name, True)


@pytest.mark.parametrize("name", ["decode_32_slots", "chunk_512"])
def test_the_index_score_kernel_waits_for_the_bytes_it_started(name):
    """`InterpretParams` counts a semaphore's bytes as the chip does: a wait
    for more than was started blocks for ever (the call runs in a thread, so
    the test fails and does not hang), one for less leaves keys behind."""
    import threading
    from jax.experimental.pallas import tpu as pltpu
    err = []

    def run():
        try:
            _index_kernel_against_its_jnp_form(name, pltpu.InterpretParams())
        except BaseException as e:     # noqa: BLE001 — reported below
            err.append(e)
        else:
            err.append(None)
    call = threading.Thread(daemon=True, target=run)
    call.start()
    call.join(300)
    assert err, "a wait that no copy satisfies"
    if err[0] is not None:
        raise err[0]


@pytest.mark.parametrize("S,T", [(3, 1), (2, 16), (1, 8)])
def test_the_latent_attention_kernel_matches_its_jnp_form(S, T):
    """The masked walk through the Pallas interpreter against the jnp form,
    over three groups of blocks, with a recycled block full of NaN that no row
    selects, and a row that selects nothing."""
    rng = np.random.default_rng(S * 100 + T)
    H, W_, rank, bs, NB, MB = 8, 256, 128, 16, 60, 48
    N = MB * bs
    q = jnp.asarray(rng.normal(size=(S, T, H, W_)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(NB, bs, W_)), jnp.float32).at[5].set(jnp.nan)
    tables = np.stack([rng.permutation(NB)[:MB] for _ in range(S)]).astype(np.int32)
    pos = rng.integers(0, N, (S, T)).astype(np.int32)
    picked = (rng.random((S, T, N)) < 0.3) \
        & (np.arange(N)[None, None, :] <= pos[:, :, None])
    for s in range(S):
        for j in np.flatnonzero(tables[s] == 5):
            picked[s, :, j * bs:(j + 1) * bs] = False
    picked[0, 0, :] = False
    args = (q, pool, jnp.asarray(tables), jnp.asarray(picked), jnp.asarray(pos))
    want = np.asarray(sl.latent_attention(*args, block_size=bs, rank=rank,
                                          scale=0.1, use_kernel=False))
    got = np.asarray(sl.latent_attention(*args, block_size=bs, rank=rank,
                                         scale=0.1, interpret=True))
    assert np.isfinite(got).all() and np.abs(got - want).max() < 1e-5
    assert (got[0, 0] == 0).all()                # nothing selected: zeros


# -- what the spans carry --------------------------------------------------------

def test_the_spans_count_the_selected_positions():
    _, eng = _engine()
    for start, tokens in [(0, 5), (0, 8), (4, 8), (8, 8), (40, 3)]:
        want = sum(min(p + 1, TOPK) for p in range(start, start + tokens))
        assert eng._chunk_counts(start, tokens, 8)["selected_tokens"] == want
    srv = GenerationServer(eng)
    try:
        eng.pos[:] = [3, 20, 11]
        eng.active[:] = [True, True, False]
        counts = srv._launch_counts()
        assert counts["rows"] == 2 and counts["live_tokens"] == 4 + 21
        assert counts["selected_tokens"] == 4 + TOPK
        eng.pos[:] = 0
        eng.active[:] = False
    finally:
        srv.shutdown(drain=False, timeout=30)
    # a model that selects nothing carries no such count
    paddle.seed(1)
    llama = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                   max_slots=2, max_seq=32, block_size=4)
    assert "selected_tokens" not in llama._chunk_counts(0, 5, 8)


def test_the_spans_count_the_index_blocks_the_kernel_copies():
    """`index_blocks`: each slot's key blocks up to its last row, in each of
    the two indexer layers (`full`, three `shared`, `full`), on a decode span
    and a prompt chunk's; a model with no indexer carries none."""
    _, eng = _engine()
    assert eng.index_layers == 2
    for start, tokens in [(0, 1), (0, 8), (3, 2), (8, 8), (40, 3)]:
        assert eng._chunk_counts(start, tokens, 8)["index_blocks"] \
            == 2 * -(-(start + tokens) // 4)
    srv = GenerationServer(eng)
    try:
        eng.pos[:] = [3, 20, 11]
        eng.active[:] = [True, True, False]
        # blocks of 4: position 3 reads block 0, position 20 blocks 0..5
        assert srv._launch_counts()["index_blocks"] == 2 * (1 + 6)
        eng.pos[:] = 0
        eng.active[:] = False
    finally:
        srv.shutdown(drain=False, timeout=30)
    llama = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                   max_slots=2, max_seq=32, block_size=4)
    assert llama.index_layers == 0
    assert "index_blocks" not in llama._chunk_counts(0, 5, 8)


def test_a_launch_hands_back_the_positions_its_rows_attended(monkeypatch):
    """Every decode launch returns, beside the tokens, the selection of each
    slot's row by indexer layer, as a bitset: the set the model's own
    `selection_mask` gives for that row of the whole sequence; a model that
    selects nothing returns none, and its spans no `walk_tokens` it has no kernel
    for."""
    model, eng = _engine()
    model_cfg = model.config
    rng = np.random.default_rng(3)
    ids = rng.integers(0, model_cfg.vocab_size, 21).astype(np.int32)
    seq = list(ids) + [eng.prefill(1, ids, budget=4)]
    got = []
    for _ in range(3):
        launch = eng.step_enqueue()
        seq.append(int(eng.step_collect(launch)[0][1]))
        got.append({li: sc.bitset_positions(np.asarray(words)[1])
                    for li, words in launch["selected"].items()})
    x = jnp.take(eng.params["emb"], jnp.asarray(np.asarray(seq[:-1])), axis=0)
    lp = eng.params["layers"][0]       # the first indexer layer reads the embedding
    xn = gm.rms_norm(x, lp["in_norm"], model_cfg.rms_norm_eps)
    c_q = gm.attention_inputs(model_cfg, lp, xn[None], jnp.arange(len(seq) - 1)[None])[0]
    want = np.asarray(gm.selection_mask(model_cfg, lp, xn[None], c_q,
                                        jnp.arange(len(seq) - 1)[None]))[0]
    for r, sel in enumerate(got):
        assert sorted(sel) == [0, 4]
        row = len(ids) + r                 # the row that reads served token r
        assert all(len(at) == min(row + 1, TOPK) for at in sel.values())
        assert np.array_equal(sel[0], np.flatnonzero(want[row]))
    # the bitset's two halves agree on a width that is no multiple of 32
    mask = rng.random((3, 75)) < 0.3
    words = np.asarray(sc.positions_bitset(jnp.asarray(mask)))
    assert words.shape == (3, 3) and words.dtype == np.uint32
    for r in range(3):
        assert np.array_equal(sc.bitset_positions(words[r]), np.flatnonzero(mask[r]))
    srv = GenerationServer(eng)
    try:
        assert "walk_tokens" not in srv._launch_counts()
        # the pools' gauges are set when a count has moved, not every pass
        sets = []
        monkeypatch.setattr(sc, "set_pool_gauges", sets.append)
        srv._pool_gauged = None
        srv._set_gauges()
        srv._set_gauges()
        assert sets == [eng.pool_blocks_in_use()]
        eng.release(1)
        srv._set_gauges()
        assert sets[1:] == [{"latent": 0, "index": 0}]
    finally:
        srv.shutdown(drain=False, timeout=30)
    paddle.seed(1)
    llama = PagedLlamaDecodeEngine(LlamaForCausalLM(LlamaConfig.tiny()),
                                   max_slots=2, max_seq=32, block_size=4)
    llama.prefill(0, [3, 4, 5], budget=4)
    assert llama.step_enqueue()["selected"] == {}
