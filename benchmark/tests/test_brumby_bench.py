"""What the Brumby cell added to the benchmark: the new cell rehearses end to end,
fails at once where the manifest lacks it, the manifest lists it where it
must, the configuration keeps every published key of the catalog row but the
depth, `lib/flops_brumby.py` agrees with a count by hand (the cell's arithmetic),
the new readers do what their files say on spans made by hand, and the
reference's rows carry the states that the serving calls reproduce in a
float32 pool and miss in a bfloat16 one."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import run as harness
from benchmark.lib import flops_brumby as F
from benchmark.lib import reference_brumby as R
from benchmark.lib import weights_brumby as W
from benchmark.readers import retention as readers
from benchmark.runners import serve_paged_state as runner

ROOT = harness.ROOT
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
NEW = "serve.brumby14b.longdoc_closed32"
CONFIG = "brumby-14b-base.d4"
MINE = {"retention_step_share.serve", "retention_step_roofline.serve",
        "retention_chunk_share.serve", "retention_chunk_roofline.serve"}
STEP = {"mfu.serve_retention", "hbm_stream_share.serve_retention"}


def _cfg():
    return harness.load_json(ROOT, "benchmark", "configs", CONFIG + ".json")


@pytest.mark.parametrize("trace", [1, 0])
def test_the_new_cell_rehearses(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         NEW, "--seed", str(2**31 + 40), "--seconds", "2", "--trace", str(trace),
         "--rehearsal"], cwd=ROOT, env=ENV, capture_output=True, text=True,
        timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and line["attempted"] > 0 and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    assert line["counts"]["state_read"] == 1
    assert set(line["compared"]) == {"served_logit_gap", "state_gap",
                                     "recurrence_gap"}
    assert line["compared"]["recurrence_gap"]["value"] < 1e-5
    assert all(c["ok"] for c in line["compared"].values())
    if trace:
        read = set(line["counts"]["per_layer_read"])
        assert {"tokens_per_step.serve", "peak_hbm_gb.serve",
                "device_idle_share.serve"} <= read
        # no kernel's device events on the CPU: its shares are left out
        assert not MINE & read


def test_on_a_manifest_without_the_cell_the_command_fails_at_once(tmp_path):
    """A checkout whose manifest lacks the cell: `no workload`, exit 1, before
    JAX is imported. (With this PR's benchmark files laid over the parent, the
    runner fails on the model's import instead, as quickly.)"""
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
    (cell,) = [w for w in m["workloads"] if w["name"] == NEW]
    assert cell["chips"] == 1
    assert cell["config"] == CONFIG and cell["traffic"] == "longdoc_closed32"
    ends = {e["name"] for e in harness.metrics_of(m, "end_to_end", NEW)}
    assert {"setup_s", "serve_tokens_per_s"} <= ends <= {
        "setup_s", "serve_tokens_per_s", "ttft_ms_p95", "token_gap_ms_p95"}
    # the runner reports a tail exactly where the manifest lists the cell
    assert runner._listed_tails(NEW) == ends & set(runner.TAILS)
    mine = {e["name"] for e in m["per_layer"] if NEW in e["workloads"]}
    assert all(e["moves"] in ends for e in m["per_layer"] if e["name"] in mine)
    new = [e for e in m["per_layer"] if e["workloads"] == [NEW]]
    assert {e["name"] for e in new} == MINE | STEP
    assert all(e["moves"] == "serve_tokens_per_s" and e["unit"] == "%"
               and e["source"] == ("device_trace" if e["name"] in MINE
                                   else "program_span") for e in new)
    assert {"tokens_per_step.serve", "compiles_in_window.serve",
            "compile_ms_in_window.serve", "device_idle_share.serve",
            "peak_hbm_gb.serve", "idle_commit_share.serve",
            "idle_sweep_share.serve", "idle_admit_share.serve",
            "idle_launch_prep_share.serve", "idle_unnamed_share.serve",
            "idle_in_fetch_share.serve", "gc_idle_share.serve",
            "gc_ms_in_window.serve", "loop_host_ms.serve"} <= mine
    # no paged kernel, no experts, no MFU of another model's reader
    assert not {"paged_attn_share.serve", "moe_share.serve", "mfu.serve",
                "mfu.serve_kda", "hbm_stream_share.serve"} & mine
    for e in new:       # every metric's file names a reader that exists
        spec = harness.load_json(ROOT, "benchmark", "metrics", e["name"] + ".json")
        mod, fn = spec["reader"].rsplit(".", 1)
        assert mod in ("retention", "common") and spec["name"] == e["name"]
        assert callable(getattr(__import__(f"benchmark.readers.{mod}",
                                           fromlist=[fn]), fn))
    mix = harness.load_json(ROOT, "benchmark", "traffic", "longdoc_closed32.json")
    assert mix["kind"] == "serve_closed_loop" and mix["clients"] == 32
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.7, "min": 1024, "max": 24576}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.5, "min": 256, "max": 4096}
    assert (mix["ramp_seconds"], mix["run_seconds"]) == (12, 51)


# the published config.json of manifestai/Brumby-14B-Base, as the model
# catalog holds it
PUBLISHED = {
    "attention_bias": False,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 5120,
    "intermediate_size": 17408,
    "max_position_embeddings": 32768,
    "max_window_layers": 40,
    "model_type": "brumby",
    "num_attention_heads": 40,
    "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06,
    "rope_scaling": None,
    "rope_theta": 1000000,
    "sliding_window": None,
    "tie_word_embeddings": False,
    "use_sliding_window": False,
    "vocab_size": 151936
}


def test_the_configuration_keeps_every_published_key_but_the_depth():
    m = harness.load_json(ROOT, "BENCHMARK.json")
    cfg = _cfg()
    for k, v in PUBLISHED.items():
        assert cfg[k] == v or k == "num_hidden_layers", k
    assert cfg["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json")
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == sorted(cfg["reduced"]) == ["num_hidden_layers"]
    assert (cfg["reduced"]["num_hidden_layers"]["published"],
            cfg["num_hidden_layers"]) == (40, 4)
    # every width is the row's; the vocabulary is whole
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"]) == (5120, 17408, 128, 40, 8, 151936)
    assert cfg["model_type"] == "brumby" and cfg["runner"] == "serve_paged_state"
    assert {"degree", "retention", "gate", "rope_and_norms", "normaliser",
            "state", "state_layout", "seeded_gate"} <= set(cfg["assumed"])
    assert "10 pipeline stages of 4" in cfg["deployment"]
    serve = cfg["serve"]
    assert (serve["max_slots"], serve["max_seq"], serve["prefill_chunk"],
            serve["state_dtype"]) == (32, 32768, 512, "float32")
    assert "num_blocks" not in serve
    c = runner.model_config(cfg, "bfloat16")
    assert (c.num_attention_heads // c.num_key_value_heads, c.feature_dim) \
        == (5, 8256)


def test_flops_against_a_count_by_hand():
    """The cell's arithmetic: 5.75 GB of weights, 136.3 MB of state a slot
    (34.08 MB a layer), 4.36 GB for 32 slots, a 32-slot decode step of 12.9 GB
    (67% of it state), a 512-row chunk of about 2.4 TFLOP (the head a third,
    the retention about a tenth)."""
    cfg = _cfg()
    h, i, v = 5120, 17408, 151936
    layer = (2 * h * 5120 + 2 * h * 1024 + 3 * h * i + h * 8 + 8 + 2 * h
             + 2 * 128)
    assert F.layer_params(cfg) == layer
    assert F.params(cfg) == 4 * layer + 2 * h * v
    assert abs(2 * F.params(cfg) - 5.75e9) < 0.01e9
    per_layer = 8 * (8256 * 128 + 8256) * 4
    assert per_layer == 34080768                   # 34.08 MB
    assert F.state_slot_bytes(cfg) == 4 * per_layer
    assert abs(32 * F.state_slot_bytes(cfg) - 4.36e9) < 0.01e9
    step = F.launch_bytes(cfg, 32, 0)
    assert step == 2 * (4 * layer + h * v) + 2 * 32 * 4 * per_layer
    assert abs(step - 12.9e9) < 0.1e9
    assert 0.66 < 2 * 32 * 4 * per_layer / step < 0.69
    # a chunk streams the weights once and its one slot's state each way
    assert F.launch_bytes(cfg, 0, 1) == 2 * (4 * layer + h * v) \
        + 2 * 4 * per_layer
    ops, nbytes = F.retention_step_cost(cfg, 32)
    assert ops == 4 * 32 * (2 * 8 * 8256 * 128 + 2 * 40 * 8256 * 128
                            + 2 * 48 * 8256)
    assert nbytes == 2 * 32 * 4 * per_layer \
        + 4 * 32 * (40 * 128 + 2 * 8 * 128 + 8 + 2 * 40 * 128) * 4
    pairs = 512 * 513 / 2
    ops, nbytes = F.retention_chunk_cost(cfg, 4, 1)
    assert ops == pytest.approx(4 * (2 * 40 * pairs * 2 * 128
                                     + 2 * 512 * 40 * 8256 * 129
                                     + 2 * 512 * 8 * 8256 * 129))
    assert nbytes == 2 * 4 * per_layer \
        + 4 * 512 * (40 * 128 + 2 * 8 * 128 + 8 + 40 * 128) * 4
    # a 512-row chunk with the head on every row: 2.4 TFLOP, the head a
    # third, the retention a tenth
    whole = F.launch_flops(cfg, 512, 512, 0, 4, 1)
    assert whole == pytest.approx(2 * 512 * (4 * layer + h * v) + ops)
    assert abs(whole - 2.4e12) < 0.15e12
    assert 0.30 < 2 * 512 * h * v / whole < 0.36
    assert 0.08 < ops / whole < 0.12
    # a decode launch: 32 rows through layers and head, the step kernel's work
    assert F.launch_flops(cfg, 32, 32, 32, 0, 0) == pytest.approx(
        2 * 32 * (4 * layer + h * v) + F.retention_step_cost(cfg, 32)[0])
    # two row tiles in two chunks count their causal pairs a chunk
    assert F.retention_chunk_cost(cfg, 2, 2)[0] \
        == pytest.approx(2 * F.retention_chunk_cost(cfg, 1, 1)[0])


def test_the_new_readers_on_spans_made_by_hand(monkeypatch):
    cfg = _cfg()
    spans = [
        (0, 10, "serving.decode", {"rows": 30, "state_slots": 30}),
        (10, 20, "serving.prefill", {"tokens": 512, "start": 1024,
                                     "state_subchunks": 4}),
        (20, 30, "serving.prefill", {"tokens": 100, "start": 0,
                                     "state_subchunks": 1}),
        (30, 40, "serving.decode", {"rows": 3}),        # no state: not read
        (40, 50, "serving.decode", {"rows": 0, "state_slots": 0}),
    ]
    prog = {"window_ns": (0, int(1e9)), "idle_ns": {}, "spans": spans}
    monkeypatch.setattr(readers, "_program", lambda obs: prog)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"ops": {"_retention_step_call.2 y": {"seconds": 0.01, "count": 4,
                                                  "text": ""},
                     "_retention_chunk_call.3 z": {"seconds": 0.02, "count": 8,
                                                   "text": ""}},
             "n_devices": 1, "busy_s": 0.5}
    obs = {"config": cfg, "peaks": peaks, "trace": trace, "run": {}}
    launches = readers._launches(obs)
    assert [l["states"] for l in launches] == [30, 0, 0, 0]
    assert [l["subchunks"] for l in launches] == [0, 4, 1, 0]
    ops, moved = F.retention_step_cost(cfg, 30)
    assert moved / 819e9 > ops / 197e12                   # memory-bound
    assert readers.retention_step_roofline(obs, "_retention_step_call") \
        == pytest.approx(100 * (moved / 819e9) / 0.01)
    least = sum(max(o / 197e12, b / 819e9) for o, b in (
        F.retention_chunk_cost(cfg, 4, 1), F.retention_chunk_cost(cfg, 1, 1)))
    assert readers.retention_chunk_roofline(obs, "_retention_chunk_call") \
        == pytest.approx(100 * least / 0.02)
    # the whole step's shares over the 1-s window: the decode launch over 30
    # slots and two chunks; the empty launch moves nothing
    nbytes = F.launch_bytes(cfg, 30, 0) + 2 * F.launch_bytes(cfg, 0, 1)
    assert readers.hbm_stream_share(obs) == pytest.approx(100 * nbytes / 819e9)
    need = (F.launch_flops(cfg, 30, 30, 30, 0, 0)
            + F.launch_flops(cfg, 512, 0, 0, 4, 1)
            + F.launch_flops(cfg, 100, 0, 0, 1, 1))
    assert readers.mfu(obs) == pytest.approx(100 * need / 197e12)
    # a kernel that left no event, or a program without these spans: None
    assert readers.retention_step_roofline(obs, "_no_such_call") is None
    monkeypatch.setattr(readers, "_program", lambda obs: None)
    for read in (readers.retention_step_roofline,
                 readers.retention_chunk_roofline):
        assert read(obs, "_retention_step_call") is None
    assert readers.mfu(obs) is None and readers.hbm_stream_share(obs) is None


def _tiny_cfg():
    cfg = dict(_cfg())
    cfg.update(runner.TINY, dtype="float32")
    return cfg


def test_the_reference_carries_the_states_the_serving_calls_reach():
    """The reference's rows: a zero gap for its own first choice, every layer's
    `(S, z)` after the asked tokens; the serving calls fed the reference's own
    rows of the last layer land on its state in a float32 pool (1e-5) and
    1e-3 and more away in a bfloat16 one, leaving the pool's other slots as
    they were."""
    cfg = _tiny_cfg()
    seed = W.seed_u32(5)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg["vocab_size"], 40).astype(np.int32)
    layers = [W.make_layer(cfg, jnp.float32)(seed, i)
              for i in range(cfg["num_hidden_layers"])]
    ends = W.make_ends(cfg, jnp.float32)(seed)
    seq = list(prompt)
    for _ in range(6):          # the reference's own greedy stream
        logits, _ = R.forward_logits(cfg, layers, *ends,
                                     jnp.asarray(np.asarray(seq, np.int32)))
        seq.append(int(np.asarray(logits)[-1].argmax()))
    seq = np.asarray(seq, np.int32)
    (row,) = R.served_logit_gaps(cfg, seed, [seq], [40], out_pad=8,
                                 dtype=jnp.float32, control="fp8",
                                 state_of=(0, len(seq) - 1))
    assert row["gap"].shape == row["control_gap"].shape == (6,)
    assert np.abs(row["gap"]).max() < 1e-5
    assert sorted(row["state"]) == [0, 1]
    _, want = R.forward_logits(cfg, layers, *ends, jnp.asarray(seq[:-1]))
    for li, state in row["state"].items():
        assert R.state_gap(state, want[li]) < 1e-5
    assert [tuple(x.shape) for x in row["rows"]] == [
        (45, 4, 16), (45, 2, 16), (45, 2, 16), (45, 2)]
    # a pool of 5 slots that holds other requests' states, the probe in slot 3
    S = jnp.asarray(rng.normal(size=(5, 2, 136, 16)), jnp.float32)
    z = jnp.asarray(np.abs(rng.normal(size=(5, 2, 136))), jnp.float32)
    got, others_same, pools = runner.recurrence_probe(
        row["rows"], 40, (S + 0.0, z + 0.0), 3, chunk=16)
    assert R.state_gap(got, row["state"][1]) < 1e-5 and others_same
    # the faults `state_gap` is there to catch: slot 4 served with no reset
    # goes on from the state it held; the rows in another layout
    stale, others_same, _ = runner.recurrence_probe(
        row["rows"], 40, pools, 4, chunk=16, fresh=False)
    assert R.state_gap(stale, row["state"][1]) > 0.1 and others_same
    swapped = runner.layout_swapped(got, 16)
    assert R.state_gap(swapped, row["state"][1]) > 0.5
    assert R.state_gap(runner.layout_swapped(swapped, 16), got) > 0.5
    low, others_same, _ = runner.recurrence_probe(
        row["rows"], 40, (S.astype(jnp.bfloat16), z.astype(jnp.bfloat16)), 3,
        chunk=16)
    assert 1e-3 < R.state_gap(low, row["state"][1]) < 0.1 and others_same
