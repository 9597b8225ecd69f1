"""lib/program_spans.py and readers/spans.py: the exact apportioning of idle
time on hand-built intervals, each reader on hand-built spans, what a trace
without program spans (a parent commit's) yields, and every new reader on a
rehearsal's own trace."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import flops, peaks, program_spans as ps, trace_reduce as tr
from benchmark.readers import spans as readers

ROOT = ps.ROOT
WINDOW = (0, 1000, tr.WINDOW_SPAN)


def S(t0, t1, name, /, **stats):
    return (t0, t1, name, stats)


def test_owner_is_the_innermost_span():
    spans = [S(0, 100, "serving.iter"), S(10, 40, "serving.decode"),
             S(20, 30, "serving.decode.fetch"), S(60, 80, "serving.commit"),
             S(200, 300, "serving.iter")]
    assert ps.owner_segments(spans) == [
        (0, 10, "serving.iter"), (10, 20, "serving.decode"),
        (20, 30, "serving.decode.fetch"), (30, 40, "serving.decode"),
        (40, 60, "serving.iter"), (60, 80, "serving.commit"),
        (80, 100, "serving.iter"), (200, 300, "serving.iter")]


def test_spans_of_two_threads_that_do_not_nest_still_get_one_owner():
    spans = [S(0, 50, "serving.iter"), S(30, 80, "train.step")]
    assert ps.owner_segments(spans) == [
        (0, 30, "serving.iter"), (30, 80, "train.step")]
    same_start = [S(0, 50, "serving.iter"), S(0, 20, "serving.admit")]
    assert ps.owner_segments(same_start) == [
        (0, 20, "serving.admit"), (20, 50, "serving.iter")]


def test_idle_is_apportioned_exactly():
    """Nested spans, one gap across two spans, one gap under none."""
    spans = [S(100, 400, "serving.iter", step=1),
             S(120, 200, "serving.admit"),
             S(200, 320, "serving.decode"), S(240, 320, "serving.decode.fetch"),
             S(320, 380, "serving.commit")]
    gaps = [(50, 90),            # under no span
            (150, 230),          # across admit and decode
            (300, 390),          # fetch, commit, then the iteration's own time
            (395, 420)]          # the iteration's tail, then nothing
    by = ps.apportion(gaps, spans)
    assert by == {None: 40 + 20, "serving.admit": 50, "serving.decode": 30,
                  "serving.decode.fetch": 20, "serving.commit": 60,
                  "serving.iter": 10 + 5}
    assert sum(by.values()) == sum(b - a for a, b in gaps)


def test_one_span_over_many_gaps_and_many_spans_in_one_gap():
    spans = [S(0, 100, "serving.sweep")] + [
        S(200 + 10 * i, 205 + 10 * i, "serving.admit") for i in range(5)]
    gaps = [(10, 20), (30, 40), (90, 110), (190, 260)]
    by = ps.apportion(gaps, spans)
    assert by == {"serving.sweep": 30, "serving.admit": 25, None: 10 + 45}


def _reduced(ops, spans, n_devices=1):
    return ps.reduce_spans(ops, [WINDOW], spans, n_devices)


def test_the_shares_sum_to_the_idle_share():
    ops = [[(0, 300, "%a = f32[2] add()", ""), (450, 700, "%b = f32[2] add()", ""),
            (900, 1200, "%c = f32[2] add()", "")]]
    spans = [S(250, 500, "serving.iter"), S(280, 400, "serving.commit"),
             S(400, 480, "serving.sweep"), S(600, 950, "serving.iter"),
             S(650, 800, "serving.admit"), S(800, 940, "serving.prefill"),
             S(805, 930, "serving.prefill.enqueue")]
    prog = _reduced(ops, spans)
    red = tr.reduce_events(ops, [WINDOW])
    assert sum(prog["idle_ns"].values()) == pytest.approx(
        red["idle_share"] * 1000)
    assert prog["idle_ns"] == {"serving.commit": 100, "serving.sweep": 50,
                               "serving.admit": 100, "serving.prefill": 5,
                               "serving.prefill.enqueue": 95}
    # spans that cross the window's edge are not among the whole ones
    assert [s[2] for s in prog["spans"]] == [s[2] for s in spans]
    assert _reduced(ops, spans + [S(990, 1100, "serving.iter")])["spans"] == \
        prog["spans"]


def test_two_devices_average():
    ops = [[(0, 1000, "%a = f32[2] add()", "")], []]
    prog = _reduced(ops, [S(0, 1000, "train.step")], n_devices=2)
    assert prog["idle_ns"] == {"train.step": 500.0}


def test_a_trace_without_program_spans_reads_nothing(monkeypatch):
    """What the parent commit gives: every reader returns None."""
    assert _reduced([[(0, 10, "%a = f32[2] add()", "")]], []) is None
    assert ps.reduce_spans([[]], [], [S(0, 10, "serving.iter")]) is None
    monkeypatch.setattr(ps, "load", lambda *a, **k: None)
    obs = _obs()
    assert readers.idle_share(obs, spans=["serving.commit"]) is None
    assert readers.idle_other_share(obs, other_than=[]) is None
    assert readers.span_ms(obs, span="serving.iter") is None
    assert readers.spans_ms_total(obs, span="jit.compile") is None
    assert readers.paged_attn_roofline(obs, "_paged_attention_call",
                                       "serving.decode", "serving.prefill") is None
    assert ps.load(os.path.join(ROOT, "no_such_dir")) is None


CFG = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 4,
       "intermediate_size": 11008, "num_hidden_layers": 16, "vocab_size": 64000}


def _obs(kernel_seconds=0.0, kernel_events=0):
    ops = {"_paged_attention_call.1 custom-call": {
        "seconds": kernel_seconds, "count": kernel_events, "text": ""}}
    return {"trace": {"ops": ops if kernel_events else {}, "n_devices": 1},
            "run": {}, "config": CFG, "cell": {"chips": 1},
            "peaks": peaks.PEAKS["TPU v5 lite"]}


@pytest.fixture
def loaded(monkeypatch):
    def use(prog):
        monkeypatch.setattr(ps, "load", lambda *a, **k: prog)
        return prog
    return use


def test_idle_share_readers_follow_the_metric_files(loaded):
    idle = {"serving.commit": 10.0, "serving.sweep": 20.0, "serving.admit": 5.0,
            "serving.prefill": 1.0, "serving.prefill.enqueue": 2.0,
            "serving.prefill.fetch": 4.0, "serving.decode.prepare": 3.0,
            "serving.decode.fetch": 6.0, "serving.iter": 7.0, None: 8.0}
    loaded({"window_ns": (0, 1000), "idle_ns": idle, "spans": []})
    obs = _obs()
    got = {}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]
                 if m["name"].startswith("idle_")]
    assert len(names) == 6
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
            spec = json.load(f)
        fn = getattr(readers, spec["reader"].split(".")[1])
        got[name] = fn(obs, **spec["args"])
    assert got == pytest.approx({
        "idle_commit_share.serve": 1.0, "idle_sweep_share.serve": 2.0,
        "idle_admit_share.serve": 0.5, "idle_launch_prep_share.serve": 0.6,
        "idle_in_fetch_share.serve": 1.0, "idle_unnamed_share.serve": 1.5})
    assert sum(got.values()) == pytest.approx(100.0 * sum(idle.values()) / 1000)


def test_loop_host_time_leaves_the_fetches_out(loaded):
    loaded({"window_ns": (0, 10_000_000), "idle_ns": {}, "spans": [
        S(0, 4_000_000, "serving.iter"), S(1_000_000, 3_000_000, "serving.decode"),
        S(2_000_000, 3_000_000, "serving.decode.fetch"),
        S(5_000_000, 9_000_000, "serving.iter"),
        S(5_000_000, 6_000_000, "serving.prefill.fetch"),
        S(6_500_000, 7_000_000, "serving.idle")]})
    obs = _obs()
    assert readers.span_ms(obs, span="serving.iter") == pytest.approx(4.0)
    assert readers.span_ms(obs, span="serving.iter",
                           less=[".fetch", "serving.idle"]) == pytest.approx(2.75)
    assert readers.span_ms(obs, span="train.step") is None
    assert readers.spans_ms_total(obs, span="jit.compile") == 0.0
    assert readers.spans_ms_total(obs, span="serving.idle") == pytest.approx(0.5)


def test_paged_roofline_from_the_counts_on_the_spans(loaded):
    loaded({"window_ns": (0, 1), "idle_ns": {}, "spans": [
        S(0, 1, "serving.decode", rows=32, live_tokens=20_000, max_ctx=1500, spec=0),
        S(0, 1, "serving.prefill", trace_id="req-1", slot=3, start=128, tokens=64,
          bucket=64),
        S(0, 1, "serving.prefill.enqueue")]})
    p = peaks.PEAKS["TPU v5 lite"]
    kv = flops.kv_bytes_per_token(CFG)
    assert kv == 2 * 16 * 4 * 128 * 2
    qo = 2 * 16 * 32 * 128 * 2
    decode = max((20_000 * kv + 32 * qo) / p["hbm_bytes_per_s"],
                 16 * 4 * 32 * 128 * 20_000 / p["bf16_flops"])
    pairs = 64 * 128 + 64 * 65 // 2
    prefill = max((192 * kv + 64 * qo) / p["hbm_bytes_per_s"],
                  16 * 4 * 32 * 128 * pairs / p["bf16_flops"])
    assert decode == pytest.approx((20_000 * kv + 32 * qo) / 819e9)   # memory bound
    got = readers.paged_attn_roofline(
        _obs(kernel_seconds=0.02, kernel_events=32),
        "_paged_attention_call", "serving.decode", "serving.prefill")
    assert got == pytest.approx(100.0 * (decode + prefill) / 0.02)
    assert 0 < got < 100
    # no kernel event in the trace (the jnp walk): nothing to read
    assert readers.paged_attn_roofline(_obs(), "_paged_attention_call",
                                       "serving.decode", "serving.prefill") is None


NEW_SERVE = {"idle_commit_share.serve", "idle_sweep_share.serve",
             "idle_in_fetch_share.serve",
             "idle_admit_share.serve", "idle_launch_prep_share.serve",
             "idle_unnamed_share.serve", "loop_host_ms.serve",
             "prefill_turn_wait_ms_p95.serve", "prefill_run_ms_p95.serve",
             "compile_ms_in_window.serve"}
NEW_TRAIN = {"dispatch_ms.train", "compile_ms_in_window.train"}


@pytest.mark.parametrize("cell,new", [("serve.yi9b.chat_closed32", NEW_SERVE),
                                      ("train.mistral7b.s2048", NEW_TRAIN)])
def test_every_new_reader_reads_a_rehearsals_own_trace(cell, new):
    """The kernel's roofline is the one that cannot be read here: the tiny
    engine takes the jnp walk, so the trace holds no kernel event."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         cell, "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "1",
         "--rehearsal"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert new <= set(line["counts"]["per_layer_read"])
    assert "spans: idle under" in proc.stderr or cell.startswith("train")
