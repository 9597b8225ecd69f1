"""What PR 33 added to the benchmark: the new cell rehearses end to end, the
manifest lists it where it must, `lib/flops_glm_moe_dsa.py` agrees with a count
by hand for one decode step and one chunk, the new readers do what their files
say on spans made by hand, the reference's served-token rows carry both margins,
and `index_set_overlap` falls below its limit when the program's selection is
replaced by the most recent positions."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import run as harness
from benchmark.lib import flops_glm_moe_dsa as F
from benchmark.lib import reference_glm_moe_dsa as R
from benchmark.lib import weights_glm_moe_dsa as W
from benchmark.readers import dsa as readers
from benchmark.runners import serve_paged_dsa as runner

ROOT = harness.ROOT
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
NEW = "serve.glm52.longctx_closed32"


def _cfg():
    return harness.load_json(ROOT, "benchmark", "configs", "glm-5.2.ep16.d5.json")


@pytest.mark.parametrize("trace", [1, 0])
def test_the_new_cell_rehearses(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         NEW, "--seed", str(2**31 + 33), "--seconds", "2", "--trace", str(trace),
         "--rehearsal"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["attempted"] > 0 and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert line["counts"]["long_requests_read"] >= 1
    assert set(line["compared"]) == {"served_logit_gap", "served_logit_gap_at_ties",
                                     "index_set_miss", "index_set_miss_deep"}
    assert all(c["ok"] for c in line["compared"].values())
    if trace:
        read = set(line["counts"]["per_layer_read"])
        # what needs no kernel's device events is read on the CPU too
        assert {"mfu.serve_dsa", "hbm_stream_share.serve_dsa", "dsa_kept_share.serve",
                "expert_rows_max_over_mean.serve_dsa"} <= read
        assert not {"mfu.serve", "mfu.serve_moe", "kv_window_saved_share.serve"} & read


def test_the_manifest_lists_the_new_cell_where_it_must():
    m = harness.load_json(ROOT, "BENCHMARK.json")
    # by name, never by place: the next cell is appended after this one
    (cell,) = [w for w in m["workloads"] if w["name"] == NEW]
    assert cell["chips"] == 1
    assert cell["config"] == "glm-5.2.ep16.d5" and cell["traffic"] == "long_ctx_closed32"
    # token_gap_ms_p95 is not this cell's (the runner's docstring says why), so
    # neither is a per-layer metric that moves it
    assert {e["name"] for e in harness.metrics_of(m, "end_to_end", NEW)} == {
        "setup_s", "serve_tokens_per_s", "ttft_ms_p95"}
    mine = {e["name"] for e in m["per_layer"] if NEW in e["workloads"]}
    assert all(e["moves"] != "token_gap_ms_p95" for e in m["per_layer"]
               if e["name"] in mine)
    new = [e for e in m["per_layer"] if e["workloads"] == [NEW]]
    assert {e["name"] for e in new} == {
        "mfu.serve_dsa", "hbm_stream_share.serve_dsa", "latent_attn_roofline.serve",
        "latent_attn_share.serve", "index_score_roofline.serve", "index_share.serve",
        "dsa_kept_share.serve", "moe_experts_roofline.serve_dsa",
        "expert_rows_max_over_mean.serve_dsa"}
    assert all(e["moves"] == "serve_tokens_per_s" for e in new)
    # the readers that read this cell unchanged list it; the others' do not
    assert {"moe_share.serve", "peak_hbm_gb.serve", "device_idle_share.serve",
            "tokens_per_step.serve"} <= mine
    assert not {"mfu.serve", "mfu.serve_moe", "paged_attn_share.serve",
                "kv_window_saved_share.serve", "moe_experts_roofline.serve"} & mine
    cfg, row = _cfg(), None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):    # the guide's catalog, where it is installed
        with open(catalog) as f:
            for ln in f:
                if '"name": "GLM-5.2"' in ln:
                    row = json.loads(ln)
    if row is not None:            # every published key unchanged but the cut
        for k, v in row["config"].items():
            assert cfg[k] == v or k in cfg["reduced"], k
    (entry,) = [c for c in m["configs"] if c["name"] == cell["config"]]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size", "num_nextn_predict_layers", "indexer_types",
         "mlp_layer_types"])
    mix = harness.load_json(ROOT, "benchmark", "traffic", "long_ctx_closed32.json")
    long_, short = mix["classes"]
    assert (long_["clients"], short["clients"], mix["clients"]) == (8, 24, 32)
    assert long_["prompt_len"] == {"dist": "lognormal", "median": 24576,
                                   "sigma": 0.35, "min": 12288, "max": 49152}
    assert short["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                   "sigma": 0.7, "min": 128, "max": 4096}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128, "sigma": 0.6,
                                 "min": 16, "max": 512}
    serve = cfg["serve"]
    assert serve["max_slots"] == 32 and serve["prefill_chunk"] == 512
    assert serve["max_seq"] >= 49152 + 512
    assert serve["num_blocks"] * serve["block_size"] >= 8 * 49664 + 24 * 4608


@pytest.mark.parametrize("tiny", [False, True], ids=["cell", "tiny"])
def test_every_seed_gives_a_chips_share_the_same_router_bias_values(tiny):
    """Every share of the router's score bias (a group of `n_routed_experts`
    experts) holds the same values under every seed, in an order the seed
    draws: a seed moves which expert is favoured, not how many rows the held
    share draws (what made the cell's tokens/s follow its seed)."""
    cfg = dict(_cfg(), **runner.TINY) if tiny else _cfg()
    group, n = cfg["n_routed_experts"], cfg["n_routed_experts_published"]
    names = [name for name, _ in W.leaf_specs(cfg)]
    leaf = W.make_leaf(cfg, jnp.bfloat16)
    draws = []
    for seed in (7, 2**31 + 33, 3300000602):
        for layer in (1, 4):
            b = np.asarray(leaf(W.seed_u32(seed), names.index(
                f"layers.{layer}.router_bias")).astype(jnp.float32))
            assert b.shape == (n,)
            draws.append(b.reshape(n // group, group))
    want = np.sort(draws[0][0])
    assert len(set(want)) == group and abs(want.mean()) < 1e-3   # distinct, centred
    assert 0.04 < want.std() < 0.055 and np.array_equal(want, -want[::-1])
    for d in draws:
        assert np.array_equal(np.sort(d, axis=-1), np.tile(want, (n // group, 1)))
    held = [tuple(d[0]) for d in draws]
    # the order is the seed's and the layer's (four values have 24 orders)
    assert len(set(held)) >= (2 if tiny else len(held))
    grid = W.router_bias_grid(group)
    assert np.array_equal(grid, -grid[::-1]) and grid.dtype == np.int32


def test_flops_against_a_count_by_hand():
    cfg = _cfg()
    h, nh = 6144, 64
    attn = h * 2048 + 2048 * nh * 256 + h * 576 + 512 * nh * 448 + nh * 256 * h
    index = 2048 * 32 * 128 + h * 128 + h * 32
    expert = 3 * h * 2048
    assert F.attn_params(cfg) == attn == 165019648
    assert F.indexer_params(cfg) == index and F.expert_params(cfg) == expert
    token = 5 * attn + 2 * index + 3 * h * 12288 + 4 * (h * 256 + expert)
    assert F.token_params(cfg) == token
    assert F.layer_counts(cfg) == {"layers": 5, "full": 2, "dense": 1, "sparse": 4}
    assert abs(F.total_params(cfg) - 3.8815e9) < 1e6          # the issue's 3.881 B
    # one decode step: 32 rows, 8 long slots at 24,000 and 24 short at 1,000
    ctx = [24000] * 8 + [1000] * 24
    selected = sum(min(c, 2048) for c in ctx)
    visible = sum(ctx)
    rows, moe_rows = 32, 60
    want = (2 * token * rows + 5 * selected * 2 * nh * (576 + 512)
            + 2 * visible * 2 * 32 * 128 + 2 * expert * moe_rows
            + 2 * h * 19360 * rows)
    assert F.launch_flops(cfg, rows, rows, selected, visible, moe_rows) == want
    # its bytes: weights less the experts with no row, the selected rows of
    # every layer, the index keys of the two indexer layers
    assert F.launch_weight_bytes(cfg, 40) == 2 * (token + h * 19360 + 40 * expert)
    assert F.cache_read_bytes(cfg, selected, visible) == 2 * (
        5 * 576 * selected + 2 * 128 * visible)
    # one chunk: 512 rows at positions 20,000 .. 20,511
    pairs_sel = 512 * 2048
    pairs_vis = sum(p + 1 for p in range(20000, 20512))
    want = (2 * token * 512 + 5 * pairs_sel * 2 * nh * 1088
            + 2 * pairs_vis * 2 * 32 * 128 + 2 * expert * 300)
    assert F.launch_flops(cfg, 512, 0, pairs_sel, pairs_vis, 300) == want
    assert 1.2e12 < 2 * token * 512 < 1.5e12       # the issue's 'about 1.4 TFLOP'
    ops, nbytes = F.latent_attn_cost(cfg, 512, pairs_sel, 20512)
    assert ops == 5 * pairs_sel * 2 * nh * 1088
    assert nbytes == 2 * 5 * (576 * 20512 + 512 * nh * 1088)
    ops, nbytes = F.index_score_cost(cfg, 512, pairs_vis, 20512)
    assert ops == 2 * pairs_vis * 8192
    assert nbytes == 2 * (2 * (128 * 20512 + 512 * 32 * 128) + 4 * pairs_vis)
    ops, nbytes = F.experts_cost(cfg, 300, 50)
    assert ops == 2 * expert * 300
    assert nbytes == 2 * (50 * expert + 300 * (2 * h + 3 * 2048))


def test_the_new_readers_on_spans_made_by_hand(monkeypatch):
    cfg = _cfg()
    spans = [
        (0, 10, "serving.decode", {"rows": 32, "live_tokens": 216000,
                                   "selected_tokens": 40384, "moe_rows": 260,
                                   "moe_experts_hit": 60, "moe_max_rows": 40,
                                   "dsa_selected": 80768, "dsa_visible": 432000,
                                   "moe_launches": 2}),
        (10, 20, "serving.prefill", {"tokens": 512, "start": 4096,
                                     "selected_tokens": 512 * 2048}),
        (20, 30, "serving.prefill", {"tokens": 100, "start": 0,
                                     "selected_tokens": 5050}),
        (30, 40, "serving.decode", {"rows": 3, "live_tokens": 30}),  # another model's
    ]
    prog = {"window_ns": (0, int(1e9)), "idle_ns": {}, "spans": spans}
    monkeypatch.setattr(readers, "_program", lambda obs: prog)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"ops": {"_expert_rows_matmul_call.1 x": {"seconds": 0.02, "count": 8,
                                                        "text": ""},
                     "_latent_attention_call.2 y": {"seconds": 0.05, "count": 15,
                                                    "text": ""},
                     "_index_score_call.3 z": {"seconds": 0.004, "count": 6,
                                               "text": ""}},
             "n_devices": 1, "busy_s": 0.5}
    obs = {"config": cfg, "peaks": peaks, "trace": trace, "run": {}}
    launches = readers._launches(obs)
    assert len(launches) == 3 and [l["head"] for l in launches] == [32, 0, 0]
    chunk = launches[1]
    assert chunk["visible"] == sum(p + 1 for p in range(4096, 4608))
    assert chunk["latent_rows"] == 4608 and chunk["index_keys"] == 4608
    assert launches[2]["latent_rows"] == 100          # a chunk's distinct rows
    flops = sum(F.launch_flops(cfg, l["rows"], l["head"], l["selected"],
                               l["visible"], l["moe_rows"]) for l in launches)
    assert readers.mfu(obs) == pytest.approx(100 * flops / 197e12)
    assert 0 < readers.hbm_stream_share(obs) < 100
    least = 0.0
    for l in launches:
        ops, nbytes = F.latent_attn_cost(cfg, l["rows"], l["selected"],
                                         l["latent_rows"])
        least += max(ops / 197e12, nbytes / 819e9)
    assert readers.latent_attn_roofline(obs, "_latent_attention_call") \
        == pytest.approx(100 * least / 0.05)
    assert 0 < readers.index_score_roofline(obs, "_index_score_call") < 100
    expert = 3 * 6144 * 2048
    assert readers.experts_roofline(obs, "_expert_rows_matmul_call") == pytest.approx(
        100 * max(2 * expert * 260 / 197e12,
                  2 * (60 * expert + 260 * (2 * 6144 + 3 * 2048)) / 819e9) / 0.02)
    assert readers.kept_share(obs) == pytest.approx(100 * 80768 / 432000)
    assert readers.rows_max_over_mean(obs) == pytest.approx(40 / (260 / 16))
    # a kernel that left no event: None, never 0
    assert readers.latent_attn_roofline(obs, "_no_such_call") is None
    monkeypatch.setattr(readers, "_program", lambda obs: None)
    for read in (readers.mfu, readers.hbm_stream_share, readers.kept_share,
                 readers.rows_max_over_mean):
        assert read(obs) is None
    assert readers.latent_attn_roofline(obs, "_latent_attention_call") is None
    assert readers.index_score_roofline(obs, "_index_score_call") is None
    assert readers.experts_roofline(obs, "_expert_rows_matmul_call") is None


def _tiny_cfg():
    cfg = dict(_cfg())
    cfg.update(runner.TINY, dtype="float32")
    return cfg


def test_served_logit_gaps_carries_both_margins_and_reads_zero_for_its_own_choice():
    cfg = _tiny_cfg()
    seed = W.seed_u32(5)
    layers = [W.make_layer(cfg, jnp.float32)(seed, i) for i in range(5)]
    embed, norm, head = W.make_ends(cfg, jnp.float32)(seed)
    rng = np.random.default_rng(5)
    seq = list(rng.integers(0, 128, 40))
    for _ in range(6):              # the reference's own greedy continuation
        logits = R.forward_logits(cfg, layers, embed, norm, head,
                                  jnp.asarray(np.asarray(seq, np.int32)),
                                  W.experts_held(cfg))
        seq.append(int(np.asarray(logits)[-1].argmax()))
    seq = np.asarray(seq, np.int32)
    rows = R.served_logit_gaps(cfg, seed, [seq, seq[:12]], [40, 6], out_pad=8,
                               dtype=jnp.float32, control="fp8")
    assert rows[0]["gap"].shape == (6,) and (rows[0]["gap"] == 0).all()
    assert np.isfinite(rows[0]["margin"]).all() and (rows[0]["margin"] > 0).all()
    # past index_topk 16 the selection has a margin; below it nothing is left out
    assert np.isfinite(rows[0]["sel_margin"]).all()
    assert np.isinf(rows[1]["sel_margin"]).all()
    assert rows[0]["control_gap"].shape == (6,)
    altered = seq.copy()
    altered[42] = (altered[42] + 1) % 128
    assert R.served_logit_gaps(cfg, seed, [altered], [40], out_pad=8,
                               dtype=jnp.float32)[0]["gap"][2] > 0


def test_index_set_overlap_falls_when_the_most_recent_positions_are_selected(
        monkeypatch):
    """The program's selections read as the runner reads them, at a tiny size,
    from the engine's own decode launches with every slot live:
    with the real rule every selected position is the reference's and every
    replayed token the one that was served; with the rule replaced by 'the most
    recent k positions' on the LAST indexer layer alone (the runner's control
    `recent_deep`) that layer's overlap reads far below the limit, the first
    layer's as before; with it replaced everywhere, both."""
    from paddle_tpu import serving_cache as sc
    from paddle_tpu.serving import PagedLlamaDecodeEngine
    cfg = _tiny_cfg()
    limits = harness.load_json(ROOT, "benchmark", "limits", NEW + ".json")
    limits = limits["rehearsal_limits"]
    seed = W.seed_u32(9)
    rng = np.random.default_rng(9)
    n_prompt = [60, 7, 11]

    def engine():
        return PagedLlamaDecodeEngine(runner.build_model(cfg, seed, "float32"),
                                      max_slots=3, max_seq=96, block_size=4,
                                      prefill_chunk=16)
    # what a server would have served: each prompt's own greedy continuation
    eng = engine()
    seqs = []
    for s, n in enumerate(n_prompt):
        prompt = rng.integers(0, 128, n).astype(np.int32)
        out = [eng.prefill(s, prompt, budget=12)]
        seqs.append((prompt, out))
    for _ in range(9):
        nxt = eng.step()
        for s, (_, out) in enumerate(seqs):
            out.append(int(nxt[s]))
    seqs = [np.concatenate([p, np.asarray(o, np.int32)]) for p, o in seqs]
    for s in range(3):
        eng.release(s)

    def miss(eng):
        got, faults, read = runner.served_selections(eng, seqs, n_prompt, 0, slot=1)
        assert faults == [] and read["replay_slots_live"] == 3
        assert sorted(got) == [0, 4]                      # the indexer layers
        assert not eng.active.any() and eng._kv.used_blocks() == 0
        rows = R.served_logit_gaps(cfg, seed, seqs[:1], n_prompt[:1], out_pad=16,
                                   dtype=jnp.float32, selections={0: got})
        rows[0]["sel_n"] = {li: n for li, (_, n) in got.items()}
        assert rows[0]["sel_upstream"][0].min() == np.inf   # no sparse layer before
        assert np.isfinite(rows[0]["sel_upstream"][4]).all()
        every = runner.overlap(rows, runner.WIDEN, clear_of=0.0)
        assert every == runner.overlap(rows, runner.WIDEN)
        return [1.0 - runner.overlap(rows, runner.WIDEN, li)[0] for li in (0, 4)], \
            read["replay_tokens_same_share"]

    assert miss(eng) == ([0.0, 0.0], 1.0)       # on the engine that served them
    deep = engine()
    runner.plant_most_recent(deep, 4)
    (first, last), _ = miss(deep)
    assert first == 0.0 and last > 5 * limits["index_set_miss_deep"]

    def most_recent(scores, valid, k):
        n = scores.shape[-1]
        last = jnp.sum(valid, -1, keepdims=True)          # positions 0..last-1
        cols = jnp.arange(n)
        sel = valid & (cols >= last - k)
        return sel, jnp.sum(sel, -1, dtype=jnp.int32)
    monkeypatch.setattr(sc, "select_topk", most_recent)
    (first, last), _ = miss(engine())
    assert first > 10 * limits["index_set_miss"] and last > 0.5
