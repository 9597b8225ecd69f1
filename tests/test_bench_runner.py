"""bench.py's runner has no way back to another device: an unknown
device_kind is an error (never another chip's peaks), and a workload
that raises makes the process exit non-zero. Fake-based, no workload
runs."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v5 lite")])
    assert bench._peak_flops() == 197e12 and bench._hbm_bw() == 819e9
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v99")])
    for peak in (bench._peak_flops, bench._hbm_bw):
        with pytest.raises(RuntimeError, match="TPU v99"):
            peak()
    # the explicit CPU smoke has no peaks: every utilization prints 0
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu", "cpu")])
    assert 1e12 / bench._peak_flops() == 0.0


def test_raising_workload_exits_nonzero_and_names_the_platform(
        monkeypatch, capsys):
    def boom():
        raise ValueError("seeded failure")

    monkeypatch.setattr(bench, "bench_boom", boom, raising=False)
    monkeypatch.setattr(bench, "_ARTIFACT", os.devnull)
    with pytest.raises(SystemExit) as exit_info:
        bench._run_one("bench_boom")
    assert exit_info.value.code == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["unit"] == "error" and "seeded failure" in \
        line["detail"]["error"]
    assert line["device"]["platform"] == "cpu"
