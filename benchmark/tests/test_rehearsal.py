"""`run.py --rehearsal` drives both cells end to end on the CPU and prints a
last line with no device metric in it; without the flag the command fails; a
later PR's configuration, cell, runner and per-layer metric are files of their
own and a manifest entry each."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run as harness

ROOT = harness.ROOT
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(root, *args, timeout=900):
    return subprocess.run([sys.executable, os.path.join(root, "benchmark", "run.py"),
                           *args], cwd=root, env=ENV, capture_output=True,
                          text=True, timeout=timeout)


def _last(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace", [("train.mistral7b.s2048", 1),
                                        ("serve.yi9b.chat_closed32", 1),
                                        ("serve.yi9b.chat_closed32", 0)])
def test_rehearsal_runs_and_prints_no_device_metric(cell, trace):
    line = _last(_run(ROOT, "--workload", cell, "--seed", str(2**31 + 7),
                      "--seconds", "2", "--trace", str(trace), "--rehearsal"))
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"] == {} and "breakdown" not in line
    assert "busy_s" not in line["device"]
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert all(c["ok"] and c["limit"] is not None for c in line["compared"].values())
    assert line["counts"]["compiles_in_window"] == 0
    if trace:
        assert line["counts"]["per_layer_read"] and line["counts"]["traced_ops"] > 0


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = _run(ROOT, "--workload", "train.mistral7b.s2048", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), "--workload", "train.mistral7b.s2048", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--rehearsal")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A copy of the benchmark beside the program; then one new configuration,
    traffic mix, limits file, runner, reader and per-layer metric, none of
    which touches a file that was there."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(tmp_path / "benchmark") for p in fs}
    bench = tmp_path / "benchmark"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(bench / "configs" / "yi-1.5-9b.d16.json") as f:
        cfg = json.load(f)
    cfg.update(name="newmodel.d8", runner="serve_again", num_hidden_layers=8)
    (bench / "configs" / "newmodel.d8.json").write_text(json.dumps(cfg))
    with open(bench / "traffic" / "chat_closed32.json") as f:
        mix = json.load(f)
    mix["clients"] = 16
    (bench / "traffic" / "chat_closed16.json").write_text(json.dumps(mix))
    shutil.copy(bench / "limits" / "serve.yi9b.chat_closed32.json",
                bench / "limits" / "serve.new.chat_closed16.json")
    (bench / "runners" / "serve_again.py").write_text(
        "from benchmark.runners.serve_paged import run as _run\n"
        "def run(ctx):\n    res = _run(ctx)\n"
        "    res['observed']['hello'] = 41.0\n    return res\n")
    (bench / "readers" / "newreader.py").write_text(
        "def hello(obs, plus):\n    return obs['run']['hello'] + plus\n")
    (bench / "metrics" / "hello.serve.json").write_text(json.dumps(
        {"name": "hello.serve", "reader": "newreader.hello", "args": {"plus": 1}}))
    manifest["configs"].append({"name": "newmodel.d8", "source": cfg["source"],
                                "file": "benchmark/configs/newmodel.d8.json",
                                "reduced": ["num_hidden_layers"], "why": "test"})
    manifest["workloads"].append({"name": "serve.new.chat_closed16",
                                  "config": "newmodel.d8", "traffic": "chat_closed16",
                                  "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "hello.serve", "unit": "count",
                                  "better": "higher", "source": "program_counter",
                                  "layer": "device", "moves": "serve_tokens_per_s",
                                  "workloads": ["serve.new.chat_closed16"]})
    for m in manifest["end_to_end"]:
        if "workloads" in m and "serve.yi9b.chat_closed32" in m["workloads"]:
            m["workloads"].append("serve.new.chat_closed16")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    line = _last(_run(root, "--workload", "serve.new.chat_closed16", "--seed", "5",
                      "--seconds", "2", "--trace", "1", "--rehearsal"))
    assert line["correct"] is True
    assert "hello.serve" in line["counts"]["per_layer_read"]
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(tmp_path / "benchmark") for p in fs
             if "__pycache__" not in dp}
    assert all(after[p] == t for p, t in before.items())
