"""What PR 35 added to the benchmark: the new cell rehearses end to end, the
manifest lists it where it must, the configuration keeps every published key of
the catalog row but those in `reduced`, `lib/flops_solar_open2.py` agrees with a
count by hand (ISSUE 35's table), the new readers do what their files say on
spans made by hand, the reference's rows carry the states, and the bfloat16-state
control moves `state_gap`."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import run as harness
from benchmark.lib import flops_solar_open2 as F
from benchmark.lib import reference_solar_open2 as R
from benchmark.lib import weights_solar_open2 as W
from benchmark.readers import kda as readers
from benchmark.runners import serve_paged_kda as runner

ROOT = harness.ROOT
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
NEW = "serve.solar2.reason_closed64"
CONFIG = "solar-open2-250b.ep16.d8"


def _cfg():
    return harness.load_json(ROOT, "benchmark", "configs", CONFIG + ".json")


@pytest.mark.parametrize("trace", [1, 0])
def test_the_new_cell_rehearses(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         NEW, "--seed", str(2**31 + 35), "--seconds", "2", "--trace", str(trace),
         "--rehearsal"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["attempted"] > 0 and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert line["counts"]["state_read"] == 1
    assert set(line["compared"]) == {"served_logit_gap", "served_logit_gap_at_ties",
                                     "state_gap", "recurrence_gap"}
    assert line["compared"]["recurrence_gap"]["value"] < 1e-4
    assert all(c["ok"] for c in line["compared"].values())
    if trace:
        read = set(line["counts"]["per_layer_read"])
        # what needs no kernel's device events is read on the CPU too
        assert {"mfu.serve_kda", "hbm_stream_share.serve_kda",
                "expert_rows_max_over_mean.serve_kda"} <= read
        assert not {"mfu.serve", "mfu.serve_moe", "mfu.serve_dsa"} & read


def test_on_a_manifest_without_the_cell_the_command_fails_at_once(tmp_path):
    """The parent's side of the driver's first try: `no workload`, exit 1, before
    JAX is imported."""
    m = harness.load_json(ROOT, "BENCHMARK.json")
    m["workloads"] = [w for w in m["workloads"] if w["name"] != NEW]
    (tmp_path / "benchmark").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    (tmp_path / "benchmark" / "run.py").write_text(
        open(os.path.join(ROOT, "benchmark", "run.py")).read())
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", NEW, "--seed", "1",
         "--seconds", "51", "--trace", "0"], cwd=tmp_path, env=ENV,
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1 and "no workload" in proc.stderr


def test_the_manifest_lists_the_new_cell_where_it_must():
    m = harness.load_json(ROOT, "BENCHMARK.json")
    # by name, never by place: the next cell is appended after this one
    (cell,) = [w for w in m["workloads"] if w["name"] == NEW]
    assert cell["chips"] == 1
    assert cell["config"] == CONFIG and cell["traffic"] == "reason_closed64"
    ends = {e["name"] for e in harness.metrics_of(m, "end_to_end", NEW)}
    assert {"setup_s", "serve_tokens_per_s"} <= ends <= {
        "setup_s", "serve_tokens_per_s", "ttft_ms_p95", "token_gap_ms_p95"}
    mine = {e["name"] for e in m["per_layer"] if NEW in e["workloads"]}
    # no per-layer metric of the cell moves an end-to-end metric it leaves out
    assert all(e["moves"] in ends for e in m["per_layer"] if e["name"] in mine)
    new = [e for e in m["per_layer"] if e["workloads"] == [NEW]]
    assert {e["name"] for e in new} == {
        "kda_step_share.serve", "kda_step_roofline.serve", "kda_chunk_share.serve",
        "kda_chunk_roofline.serve", "mfu.serve_kda", "hbm_stream_share.serve_kda",
        "moe_experts_roofline.serve_kda", "expert_rows_max_over_mean.serve_kda",
        "paged_attn_roofline.serve_kda"}
    assert all(e["moves"] == "serve_tokens_per_s" for e in new)
    assert {"moe_share.serve", "paged_attn_share.serve", "peak_hbm_gb.serve",
            "device_idle_share.serve", "tokens_per_step.serve"} <= mine
    assert not {"mfu.serve", "mfu.serve_moe", "mfu.serve_dsa",
                "kv_window_saved_share.serve", "latent_attn_share.serve"} & mine
    for e in new:       # every metric's file names a reader that exists
        spec = harness.load_json(ROOT, "benchmark", "metrics", e["name"] + ".json")
        mod, fn = spec["reader"].rsplit(".", 1)
        assert mod in ("kda", "common") and spec["name"] == e["name"]
        assert callable(getattr(__import__(f"benchmark.readers.{mod}",
                                           fromlist=[fn]), fn))
    mix = harness.load_json(ROOT, "benchmark", "traffic", "reason_closed64.json")
    assert mix["kind"] == "serve_closed_loop" and mix["clients"] == 64
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                                 "min": 128, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 768, "sigma": 0.6,
                                 "min": 192, "max": 2048}
    assert (mix["pool"], mix["order_seed"], mix["ramp_seconds"],
            mix["trace_seconds"], mix["run_seconds"]) == (64, 2027, 12, 8, 51)


def test_the_configuration_keeps_every_published_key_but_the_cut():
    m = harness.load_json(ROOT, "BENCHMARK.json")
    cfg, row = _cfg(), None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):    # the guide's catalog, where it is installed
        with open(catalog) as f:
            for ln in f:
                if '"name": "Solar-Open2-250B"' in ln:
                    row = json.loads(ln)
    cut = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    if row is not None:
        for k, v in row["config"].items():
            assert cfg[k] == v or k in cut, k
        assert cfg["source"] == row["source_url"]
        for k in cut:
            assert cfg["reduced"][k]["published"] == row["config"][k] \
                or k == "gqa_layers"
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(cut)
    # no width differs: the widths the program is built from are the row's
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_experts_per_tok"]) == (4096, 1280, 128, 64, 8, 8)
    assert cfg["linear_attn_config"] == {"short_conv_kernel_size": 4,
                                         "head_dim": 128, "num_heads": 64,
                                         "num_kv_heads": None}
    assert (cfg["num_hidden_layers"], cfg["gqa_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (8, [0, 4], 20, 24576)
    assert cfg["n_routed_experts_published"] == 320 and cfg["runner"] == "serve_paged_kda"
    assert {"router", "gate_low_rank", "gqa_gate", "convolutions", "qk_norm",
            "state_dtype", "num_kv_heads", "seeded_decay"} <= set(cfg["assumed"])
    assert "16 chips" in cfg["deployment"] and "8 ways" in cfg["deployment"]
    serve = cfg["serve"]
    assert (serve["max_slots"], serve["max_seq"], serve["prefill_chunk"]) == (
        64, 10240, 512)
    assert serve["num_blocks"] * serve["block_size"] == 327680
    # the program builds from it
    c = runner.model_config(cfg, "bfloat16")
    assert c.layer_kind(0) == c.layer_kind(4) == "gqa" and c.layer_kind(5) == "kda"


def test_flops_against_a_count_by_hand():
    """ISSUE 35's table, and one decode step and one chunk by hand."""
    cfg = _cfg()
    h, w = 4096, 8192
    kda = 3 * h * w + w * h + 2 * (h * 128 + 128 * w) + h * 64 + 3 * w * 4 \
        + 64 + w + 128
    gqa = h * w + 2 * h * 1024 + h * w + w * h
    expert = 3 * h * 1280
    assert F.kda_params(cfg) == kda == 137732288          # 137.73 M
    assert F.gqa_params(cfg) == gqa == 109051904          # 109.05 M
    assert F.router_params(cfg) == 320 * h + 320          # 1.31 M
    assert F.expert_params(cfg) == F.shared_params(cfg) == expert == 15728640
    assert F.layer_kinds(cfg) == (2, 6)
    # 469.3 M and 440.7 M with 20 experts held (the two norms are 8,192 more)
    assert F.layer_params(cfg, "kda") == kda + 320 * h + 320 + 21 * expert + 2 * h
    assert abs(F.layer_params(cfg, "kda") - 469.3e6) < 1e5
    assert abs(F.layer_params(cfg, "gqa") - 440.7e6) < 1e5
    assert abs(F.total_params(cfg) - 3.899e9) < 1e6       # 7.80 GB in bfloat16
    assert F.state_bytes_per_slot_layer(cfg) == 64 * 128 * 128 * 4 == 4194304
    # the state of 64 slots in 6 layers: 1.61 GB; read and written a step: 3.2
    assert F.state_stream_bytes(cfg, 64) == 2 * 6 * 64 * 4194304
    assert abs(F.state_stream_bytes(cfg, 64) - 3.22e9) < 1e7
    dense = 2 * gqa + 6 * kda + 8 * (320 * h + 320 + expert)
    assert F.dense_params(cfg) == dense
    # one decode step: 64 rows at a mean context of 1,900, 130 expert rows
    rows, live, moe_rows = 64, 64 * 1900, 130
    want = (2 * dense * rows + 6 * 2 * 7 * 128 * 128 * 64 * rows
            + 2 * 4 * 64 * 128 * live + 2 * expert * moe_rows
            + 2 * h * 24576 * rows)
    assert F.launch_flops(cfg, rows, rows, live, moe_rows) == want
    assert F.launch_weight_bytes(cfg, 128) == 2 * (dense + h * 24576 + 128 * expert)
    assert F.kv_read_bytes(cfg, live) == 2 * 4096 * live      # 4,096 B a token a layer
    ops, nbytes = F.kda_step_cost(cfg, 64)
    assert ops == 6 * 2 * 7 * 128 * 128 * 64 * 64
    assert nbytes == 2 * 6 * 64 * 4194304 + 6 * 64 * 64 * 6 * 128 * 4
    # one chunk: 512 rows at positions 1,024 .. 1,535, 8 sub-chunks
    pairs = sum(p + 1 for p in range(1024, 1536))
    want = (2 * dense * 512 + 6 * 2 * 7 * 128 * 128 * 64 * 512
            + 2 * 4 * 64 * 128 * pairs + 2 * expert * 1000)
    assert F.launch_flops(cfg, 512, 0, pairs, 1000) == want
    assert 1.2e12 < 2 * dense * 512 < 1.5e12        # the issue's 'about 1.4 TFLOP'
    ops, nbytes = F.kda_chunk_cost(cfg, 8, 1)
    assert ops == 6 * 2 * (3 * 64 * 128 * 128 + 64 * 64 * 128) * 64 * 8
    assert nbytes == 6 * (4 * 64 * 8 * (5 * 64 * 128 + 64 * 64 + 128) + 2 * 4194304)
    ops, nbytes = F.experts_cost(cfg, 300, 50)
    assert ops == 2 * expert * 300
    assert nbytes == 2 * (50 * expert + 300 * (2 * h + 3 * 1280))
    ops, nbytes = F.paged_attn_cost(cfg, 64, live, live)
    assert ops == 2 * 4 * 64 * 128 * live
    assert nbytes == 2 * 4096 * live + 2 * 2 * 64 * 64 * 128 * 2


def test_the_new_readers_on_spans_made_by_hand(monkeypatch):
    cfg = _cfg()
    spans = [
        (0, 10, "serving.decode", {"rows": 60, "live_tokens": 120000,
                                   "state_slots": 60, "moe_rows": 260,
                                   "moe_experts_hit": 110, "moe_max_rows": 40,
                                   "moe_launches": 2}),
        (10, 20, "serving.prefill", {"tokens": 512, "start": 1024,
                                     "state_subchunks": 8}),
        (20, 30, "serving.prefill", {"tokens": 100, "start": 0,
                                     "state_subchunks": 2}),
        (30, 40, "serving.decode", {"rows": 3, "live_tokens": 30}),  # another model's
    ]
    prog = {"window_ns": (0, int(1e9)), "idle_ns": {}, "spans": spans}
    monkeypatch.setattr(readers, "_program", lambda obs: prog)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"ops": {"_expert_rows_matmul_call.1 x": {"seconds": 0.02, "count": 16,
                                                        "text": ""},
                     "_kda_step_call.2 y": {"seconds": 0.006, "count": 6, "text": ""},
                     "_kda_chunk_call.3 z": {"seconds": 0.004, "count": 12,
                                             "text": ""},
                     "_paged_attention_call.4 w": {"seconds": 0.003, "count": 6,
                                                   "text": ""}},
             "n_devices": 1, "busy_s": 0.5}
    obs = {"config": cfg, "peaks": peaks, "trace": trace, "run": {}}
    launches = readers._launches(obs)
    assert len(launches) == 3 and [l["head"] for l in launches] == [60, 0, 0]
    assert [l["states"] for l in launches] == [60, 1, 1]
    assert launches[1]["pairs"] == sum(p + 1 for p in range(1024, 1536))
    flops = sum(F.launch_flops(cfg, l["rows"], l["head"], l["pairs"],
                               l["moe_rows"]) for l in launches)
    assert readers.mfu(obs) == pytest.approx(100 * flops / 197e12)
    nbytes = sum(F.launch_weight_bytes(cfg, l["moe_experts_hit"])
                 + F.kv_read_bytes(cfg, l["kv"])
                 + F.state_stream_bytes(cfg, l["states"]) for l in launches)
    assert readers.hbm_stream_share(obs) == pytest.approx(100 * nbytes / 819e9)
    # the step kernel: the decode launch's 60 states in 6 layers, memory-bound
    ops, moved = F.kda_step_cost(cfg, 60)
    assert moved / 819e9 > ops / 197e12
    assert readers.kda_step_roofline(obs, "_kda_step_call") == pytest.approx(
        100 * (moved / 819e9) / 0.006)
    least = sum(max(o / 197e12, b / 819e9) for o, b in (
        F.kda_chunk_cost(cfg, 8, 1), F.kda_chunk_cost(cfg, 2, 1)))
    assert readers.kda_chunk_roofline(obs, "_kda_chunk_call") == pytest.approx(
        100 * least / 0.004)
    expert = 3 * 4096 * 1280
    assert readers.experts_roofline(obs, "_expert_rows_matmul_call") == pytest.approx(
        100 * max(2 * expert * 260 / 197e12,
                  2 * (110 * expert + 260 * (2 * 4096 + 3 * 1280)) / 819e9) / 0.02)
    assert 0 < readers.paged_attn_roofline(obs, "_paged_attention_call") < 100
    assert readers.rows_max_over_mean(obs) == pytest.approx(40 / (260 / 20))
    # a kernel that left no event: None, never 0
    assert readers.kda_step_roofline(obs, "_no_such_call") is None
    monkeypatch.setattr(readers, "_program", lambda obs: None)
    for read in (readers.mfu, readers.hbm_stream_share, readers.rows_max_over_mean):
        assert read(obs) is None
    for read in (readers.kda_step_roofline, readers.kda_chunk_roofline,
                 readers.experts_roofline, readers.paged_attn_roofline):
        assert read(obs, "_kda_step_call") is None


def _tiny_cfg():
    cfg = dict(_cfg())
    cfg.update(runner.TINY, dtype="float32")
    return cfg


def test_served_logit_gaps_carries_the_states_and_the_control_moves_them():
    """The reference's rows: a zero gap for its own first choice, the router's
    margin, every KDA layer's state after the asked tokens; and with the state
    rounded to bfloat16 after every token (the control one step below the
    configuration's float32) the state lies 1e-3 and more from it."""
    cfg = _tiny_cfg()
    seed = W.seed_u32(5)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg["vocab_size"], 40).astype(np.int32)
    layers = [W.make_layer(cfg, jnp.float32)(seed, i)
              for i in range(cfg["num_hidden_layers"])]
    embed, norm, head = W.make_ends(cfg, jnp.float32)(seed)
    seq = list(prompt)
    for _ in range(6):          # the reference's own greedy stream
        logits, _ = R.forward_logits(cfg, layers, embed, norm, head,
                                     jnp.asarray(np.asarray(seq, np.int32)),
                                     W.experts_held(cfg))
        seq.append(int(np.asarray(logits)[-1].argmax()))
    seq = np.asarray(seq, np.int32)
    rows = R.served_logit_gaps(cfg, seed, [seq], [40], out_pad=8,
                               dtype=jnp.float32, control="state_bf16",
                               state_of=(0, len(seq) - 1))
    (row,) = rows
    assert row["gap"].shape == (6,) and not row["gap"].any()
    assert row["margin"].shape == (6,) and (row["margin"] >= 0).all()
    assert sorted(row["state"]) == [1, 2, 3, 5, 6, 7]
    _, want = R.forward_logits(cfg, layers, embed, norm, head,
                               jnp.asarray(seq[:-1]), W.experts_held(cfg))
    for li, state in row["state"].items():
        assert R.state_gap(state, want[li]) < 1e-5
        assert 1e-3 < R.state_gap(row["control_state"][li], state) < 0.1
    # the serving kernels fed the reference's own rows of the last KDA layer
    # land on its state; in a bfloat16 pool they land where the control does
    assert [tuple(x.shape) for x in row["rows"]] == [(45, 2, 16)] * 4 + [(45, 2)]
    # (a pool of 5 slots that holds other requests' states, the probe in slot 3)
    pool = jnp.asarray(rng.normal(size=(5, 2, 16, 16)), jnp.float32)
    got, others_same = runner.recurrence_probe(row["rows"], 40, pool + 0.0, 3,
                                               chunk=16)
    assert R.state_gap(got, row["state"][7]) < 1e-5 and others_same
    low, others_same = runner.recurrence_probe(
        row["rows"], 40, pool.astype(jnp.bfloat16), 3, chunk=16)
    assert 1e-3 < R.state_gap(low, row["state"][7]) < 0.1 and others_same
