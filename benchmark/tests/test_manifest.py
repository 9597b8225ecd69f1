"""BENCHMARK.json against the contract it was written to, and every file it
names."""
import importlib
import json
import os
import re

import pytest

from benchmark import run as harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
                   r"expansion|experts_per_tok")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    # a full check with the full 24 cells has to fit the driver's budget
    n = manifest["run_seconds"]
    assert (2 + 14 * 24) * (n + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(manifest["configs"]) <= 24
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in manifest["paths"])
    assert len(manifest["command"]) <= 32
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, cells // 4)


def test_names_units_and_texts(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(set(names)) == len(names)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_configs_and_their_files(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        importlib.import_module(f"benchmark.runners.{cfg['runner']}").run


def test_every_cell_reports_what_it_must(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in manifest["workloads"]:
        loaded = harness.load_cell(w["name"])      # every file the cell needs
        assert set(loaded["limits"]) >= {"limits", "rehearsal_limits"}
        mine = harness.metrics_of(manifest, "end_to_end", w["name"])
        assert len(mine) >= 2
        assert harness.metrics_of(manifest, "per_layer", w["name"])
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]
        cells = m.get("workloads") or [w["name"] for w in manifest["workloads"]]
        for cell in cells:
            assert "workloads" not in moved or cell in moved["workloads"], (m, cell)


def test_every_per_layer_metric_has_its_reader(manifest):
    for m in manifest["per_layer"]:
        spec = harness.load_json(harness.HERE, "metrics", m["name"] + ".json")
        assert spec["name"] == m["name"]
        mod, fn = spec["reader"].rsplit(".", 1)
        assert callable(getattr(importlib.import_module(f"benchmark.readers.{mod}"), fn))
    listed = {m["name"] + ".json" for m in manifest["per_layer"]}
    assert listed == set(os.listdir(os.path.join(harness.HERE, "metrics")))


def test_a_roofline_or_mfu_has_the_whole_steps_share_beside_it(manifest):
    for m in manifest["per_layer"]:
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in manifest["per_layer"])
