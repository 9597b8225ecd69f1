"""What PR 28 added to the benchmark: the two new cells rehearse end to end,
the manifest lists them where it must, `lib/flops_cohere2_moe.py` agrees with a
count by hand, `lib/reference_cohere2_moe.py` with a direct dense computation,
the two-class generator and the new readers do what their files say."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import run as harness
from benchmark.lib import flops_cohere2_moe as F
from benchmark.lib import reference_cohere2_moe as R
from benchmark.lib import traffic_two_class, weights_cohere2_moe as W
from benchmark.readers import moe as readers

ROOT = harness.ROOT
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
NEW, YI2 = "serve.cmdaplus.mixed_closed32", "serve.yi9b.decode_closed32"


def _cfg():
    return harness.load_json(ROOT, "benchmark", "configs",
                             "command-a-plus-05-2026.ep8.d4.json")


@pytest.mark.parametrize("cell,trace", [(NEW, 1), (NEW, 0), (YI2, 1), (YI2, 0)])
def test_the_new_cells_rehearse(cell, trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         cell, "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace),
         "--rehearsal"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["attempted"] > 0 and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert all(c["ok"] for c in line["compared"].values())
    if trace and cell == NEW:
        read = set(line["counts"]["per_layer_read"])
        # what needs no kernel's device events is read on the CPU too
        assert {"mfu.serve_moe", "hbm_stream_share.serve_moe",
                "expert_rows_max_over_mean.serve",
                "kv_window_saved_share.serve"} <= read
        assert not {"mfu.serve", "hbm_stream_share.serve"} & read


def test_the_manifest_lists_the_new_cells_where_it_must():
    m = harness.load_json(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in m["workloads"]]
    assert cells[-2:] == [YI2, NEW]
    assert all(w["chips"] == 1 for w in m["workloads"])
    for e in m["end_to_end"]:
        if e["name"] in ("serve_tokens_per_s", "ttft_ms_p95", "token_gap_ms_p95"):
            assert e["workloads"][-2:] == [YI2, NEW]
    dense_only = {"mfu.serve", "hbm_stream_share.serve", "paged_attn_roofline.serve"}
    for e in m["per_layer"]:
        if e["name"].endswith(".serve") and NEW not in e["workloads"][:1] \
                and "serve.yi9b.chat_closed32" in e["workloads"]:
            assert YI2 in e["workloads"]
            assert (NEW in e["workloads"]) == (e["name"] not in dense_only)
    new = [e for e in m["per_layer"] if e["workloads"] == [NEW]]
    assert len(new) == 7 and all(e["moves"] == "serve_tokens_per_s" for e in new)
    cfg, row = _cfg(), None
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for ln in f:
            if "command-a-plus-05-2026" in ln:
                row = json.loads(ln)
    if row is not None:            # every published key unchanged but the three
        for k, v in row["config"].items():
            assert cfg[k] == v or k in cfg["reduced"], k
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]


def test_flops_against_a_count_by_hand():
    cfg = _cfg()
    assert F.layer_kinds(cfg) == (1, 3)
    assert F.attn_params(cfg) == 4096 * 16384 * 2 + 2 * 4096 * 1024 == 142606336
    assert F.shared_params(cfg) == 4 * 3 * 4096 * 4096 == 201326592
    assert F.router_params(cfg) == 4096 * 128
    assert F.expert_params(cfg) == 3 * 4096 * 4096 == 50331648
    assert F.dense_layer_params(cfg) == 344457216                 # "344.4 M"
    assert abs(F.total_params(cfg) * 2 / 1e9 - 9.47) < 0.01       # "9.47 GB"
    # one decode launch: 32 rows at positions 99 (100 keys each; the window
    # does not bind), 70 pairs on held experts, 14 held experts hit
    fl = F.launch_flops(cfg, 32, 32, 3200, 3200, 70)
    by_hand = (2 * 4 * 344457216 * 32 + 4 * 128 * 128 * 3200 * 4
               + 2 * 50331648 * 70 + 2 * 4096 * 32768 * 32)
    assert fl == by_hand
    assert F.launch_weight_bytes(cfg, 14) == 2 * (4 * 344457216 + 4096 * 32768
                                                  + 14 * 50331648)
    # 8 long slots at 10000 keys: a window layer reads 4096 of them
    assert F.kv_read_bytes(cfg, 80000, 8 * 4096) == 4096 * (80000 + 3 * 8 * 4096)
    ops, nbytes = F.experts_cost(cfg, 70, 14)
    assert ops == 2 * 50331648 * 70
    assert nbytes == 2 * (14 * 50331648 + 70 * (2 * 4096 + 3 * 4096))
    ops, nbytes = F.paged_attn_cost(cfg, 32, 80000, 32768, 80000, 32768)
    assert ops == 4 * 128 * 128 * (80000 + 3 * 32768)
    assert nbytes == 4096 * (80000 + 3 * 32768) + 2 * 4 * 32 * 128 * 128 * 2


TINY = dict(hidden_size=32, head_dim=16, intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=2, num_shared_experts=2, num_experts=4,
            experts_held_from=2, num_experts_published=8, num_experts_per_tok=3,
            vocab_size=64, num_hidden_layers=4, sliding_window=6,
            layer_types=["sliding_attention"] * 3 + ["full_attention"],
            rope_theta=50000, layer_norm_eps=1e-5, logit_scale=1,
            norm_topk_prob=True)


def _dense_forward(cfg, layers, embed, norm, ids):
    """The same mathematics written another way, in float64 NumPy: one
    position and one head at a time, explicit loops over experts."""
    def ln(x, w):
        return (x - x.mean()) / np.sqrt(x.var() + cfg["layer_norm_eps"]) * w
    L, d, nh, kvh = len(ids), cfg["head_dim"], 4, 2
    lo, hi = W.experts_held(cfg)
    x = np.asarray(embed, np.float64)[ids]
    for li, lp in enumerate(layers):
        lp = {k: np.asarray(v, np.float64) for k, v in lp.items()}
        n = np.stack([ln(r, lp["norm"]) for r in x])
        q = (n @ lp["q"].T).reshape(L, nh, d)
        k = (n @ lp["k"].T).reshape(L, kvh, d)
        v = (n @ lp["v"].T).reshape(L, kvh, d)
        window = cfg["layer_types"][li] == "sliding_attention"
        if window:
            inv = 1.0 / cfg["rope_theta"] ** (np.arange(d // 2) / (d // 2))
            for t in (q, k):
                for p in range(L):
                    c, s = np.cos(p * inv), np.sin(p * inv)
                    e, o = t[p, :, 0::2].copy(), t[p, :, 1::2].copy()
                    t[p, :, 0::2], t[p, :, 1::2] = e * c - o * s, o * c + e * s
        a = np.zeros((L, nh * d))
        for i in range(L):
            first = max(0, i - cfg["sliding_window"] + 1) if window else 0
            for h in range(nh):
                g = h // (nh // kvh)
                sc = k[first:i + 1, g] @ q[i, h] / np.sqrt(d)
                p = np.exp(sc - sc.max())
                a[i, h * d:(h + 1) * d] = (p / p.sum()) @ v[first:i + 1, g]
        m = np.zeros_like(x)
        inter = cfg["intermediate_size"]
        for i in range(L):
            s = 1 / (1 + np.exp(-(lp["router"] @ n[i])))
            top = np.argsort(-s)[:cfg["num_experts_per_tok"]]
            for e in top:
                if lo <= e < hi:
                    gu = n[i] @ lp["experts_gate_up"][e - lo]
                    act = gu[:inter] / (1 + np.exp(-gu[:inter])) * gu[inter:]
                    m[i] += s[e] / s[top].sum() * (act @ lp["experts_down"][e - lo])
            for j in range(cfg["num_shared_experts"]):
                r = slice(j * inter, (j + 1) * inter)
                g, u = lp["shared_gate"][r] @ n[i], lp["shared_up"][r] @ n[i]
                m[i] += (g / (1 + np.exp(-g)) * u) @ lp["shared_down"][:, r].T \
                    / cfg["num_shared_experts"]
        x = x + a @ lp["o"].T + m
    x = np.stack([ln(r, np.asarray(norm, np.float64)) for r in x])
    return x @ np.asarray(embed, np.float64).T


def test_the_reference_against_a_direct_dense_computation():
    cfg, seed = dict(TINY), W.seed_u32(3)
    layers = [W.make_layer(cfg, jnp.float32)(seed, i) for i in range(4)]
    embed, norm = W.make_ends(cfg, jnp.float32)(seed)
    ids = np.random.default_rng(0).integers(0, 64, 21).astype(np.int32)
    old, R.Q_BLOCK = R.Q_BLOCK, 8          # several query blocks, a ragged last
    try:
        got = np.asarray(R.forward_logits(cfg, layers, embed, norm,
                                          jnp.asarray(ids), W.experts_held(cfg)))
    finally:
        R.Q_BLOCK = old
    want = _dense_forward(cfg, layers, embed, norm, ids)
    assert np.abs(got - want).max() < 1e-4 * want.std() + 1e-5
    assert want.std() > 0.01


def test_the_fp8_control_moves_the_logits_and_the_router_stays_float32():
    cfg, seed = dict(TINY), W.seed_u32(3)
    layers = [W.make_layer(cfg, jnp.float32)(seed, i) for i in range(4)]
    embed, norm = W.make_ends(cfg, jnp.float32)(seed)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, 16), jnp.int32)
    held = W.experts_held(cfg)
    ref = np.asarray(R.forward_logits(cfg, layers, embed, norm, ids, held))
    low = np.asarray(R.forward_logits(cfg, layers, embed, norm, ids, held, "fp8"))
    assert 1e-3 < np.abs(ref - low).max() / ref.std() < 1.0
    n = jnp.asarray(np.random.default_rng(2).normal(size=(5, 32)), jnp.float32)
    w, margin = R.route(n, layers[0]["router"].astype(jnp.float32), 3)
    assert (np.asarray(w) > 0).sum(-1).tolist() == [3] * 5
    assert np.allclose(np.asarray(w).sum(-1), 1, atol=1e-6) and (margin >= 0).all()


def test_served_logit_gaps_reads_zero_for_the_references_own_choice():
    cfg, seed = dict(TINY), W.seed_u32(5)
    layers = [W.make_layer(cfg, jnp.float32)(seed, i) for i in range(4)]
    embed, norm = W.make_ends(cfg, jnp.float32)(seed)
    seq = list(np.random.default_rng(4).integers(0, 64, 9))
    for _ in range(5):
        lg = R.forward_logits(cfg, layers, embed, norm,
                              jnp.asarray(seq, jnp.int32), W.experts_held(cfg))
        seq.append(int(jnp.argmax(lg[-1])))
    old, R.SEQ_BUCKET = R.SEQ_BUCKET, 16
    try:
        rows = R.served_logit_gaps(cfg, seed, [np.asarray(seq, np.int32)], [9],
                                   out_pad=8, dtype=jnp.float32, control="fp8")
        wrong = np.asarray(seq, np.int32)
        wrong[11] = (wrong[11] + 1) % 64
        bad = R.served_logit_gaps(cfg, seed, [wrong], [9], out_pad=8,
                                  dtype=jnp.float32)
    finally:
        R.SEQ_BUCKET = old
    assert rows[0]["gap"].shape == (5,) and (rows[0]["gap"] == 0).all()
    assert rows[0]["control_gap"].shape == (5,) and (rows[0]["margin"] >= 0).all()
    assert bad[0]["gap"][2] > 0.1


def test_the_two_class_generator():
    mix = harness.load_json(ROOT, "benchmark", "traffic", "mixed_len_closed32.json")
    classes = traffic_two_class.client_classes(mix)
    assert classes == [0] * 8 + [1] * 24
    a, b = traffic_two_class.streams(mix, 32768, 2**31 + 5)
    longs = [next(a) for _ in range(16)]
    shorts = [next(b) for _ in range(48)]
    lens = sorted(len(r["prompt"]) for r in longs[:8])
    assert lens == sorted(len(r["prompt"]) for r in longs[8:])     # same multiset
    assert 4608 <= lens[0] and lens[-1] <= 14336 and lens[0] > 4096
    assert all(64 <= len(r["prompt"]) <= 2048 for r in shorts)
    assert all(16 <= r["max_new"] <= 512 for r in longs + shorts)
    assert max(len(r["prompt"]) + r["max_new"] for r in longs) <= 14848
    assert all(int(r["prompt"].max()) < 32768 for r in longs)
    # the order is the mix's, the ids the seed's
    a2, _ = traffic_two_class.streams(mix, 32768, 7)
    again = [next(a2) for _ in range(8)]
    assert [len(r["prompt"]) for r in again] == [len(r["prompt"]) for r in longs[:8]]
    assert not np.array_equal(again[0]["prompt"][:32], longs[0]["prompt"][:32])


def test_the_new_readers_on_spans_made_by_hand(monkeypatch):
    cfg = _cfg()
    spans = [
        (0, 10, "serving.decode", {"rows": 32, "live_tokens": 90000,
                                   "window_tokens": 40000, "moe_rows": 260,
                                   "moe_experts_hit": 60, "moe_max_rows": 40,
                                   "moe_launches": 2}),
        (10, 20, "serving.prefill", {"tokens": 512, "start": 4096,
                                     "window_tokens": 4607}),
        (20, 30, "serving.prefill", {"tokens": 100, "start": 0, "window_tokens": 100,
                                     "moe_rows": 90, "moe_experts_hit": 50,
                                     "moe_max_rows": 12, "moe_launches": 1}),
        (30, 40, "serving.decode", {"rows": 3, "live_tokens": 30}),   # no window key
    ]
    prog = {"window_ns": (0, int(1e9)), "idle_ns": {}, "spans": spans}
    monkeypatch.setattr(readers, "_program", lambda obs: prog)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"ops": {"_expert_rows_matmul_call.1 x": {"seconds": 0.02, "count": 8,
                                                        "text": ""},
                     "_paged_attention_call.2 y": {"seconds": 0.004, "count": 8,
                                                   "text": ""}},
             "n_devices": 1, "busy_s": 0.5}
    obs = {"config": cfg, "peaks": peaks, "trace": trace,
           "run": {"kv_blocks": [{"full": 1000.0, "window": 400.0},
                                 {"full": 2000.0, "window": 500.0}]}}
    assert readers._window_pairs(4096, 512, 4096) == 512 * 4096
    assert readers._window_pairs(0, 100, 4096) == 100 * 101 // 2
    assert readers._window_pairs(4000, 200, 4096) == sum(
        min(p + 1, 4096) for p in range(4000, 4200))
    launches = readers._launches(obs)
    assert len(launches) == 3 and [l["head"] for l in launches] == [32, 0, 1]
    flops = sum(F.launch_flops(cfg, l["rows"], l["head"], l["full_pairs"],
                               l["window_pairs"], l["moe_rows"]) for l in launches)
    assert readers.mfu(obs) == pytest.approx(100 * flops / 197e12)
    assert 0 < readers.hbm_stream_share(obs) < 100
    least = sum(max(2 * 50331648 * r / 197e12,
                    2 * (h * 50331648 + r * 5 * 4096) / 819e9)
                for r, h in ((260, 60), (90, 50)))
    assert readers.experts_roofline(obs, "_expert_rows_matmul_call") \
        == pytest.approx(100 * least / 0.02)
    assert 0 < readers.paged_attn_roofline(obs, "_paged_attention_call") < 100
    assert readers.rows_max_over_mean(obs) == pytest.approx(52 / (350 / 16))
    assert readers.kv_window_saved_share(obs) == pytest.approx(
        100 * ((1 - (1000 + 1200) / 4000) + (1 - (2000 + 1500) / 8000)) / 2)
    # nothing to read: None, never 0
    monkeypatch.setattr(readers, "_program", lambda obs: None)
    assert readers.mfu(obs) is None and readers.hbm_stream_share(obs) is None
    assert readers.experts_roofline(obs, "_expert_rows_matmul_call") is None
    assert readers.paged_attn_roofline(obs, "_paged_attention_call") is None
    assert readers.rows_max_over_mean(obs) is None
    assert readers.kv_window_saved_share({"run": {}, "config": cfg}) is None
