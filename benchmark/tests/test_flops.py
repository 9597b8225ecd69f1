"""lib/flops.py against counts made by hand for both configurations."""
import json
import os

from benchmark import run as harness
from benchmark.lib import flops, peaks


def _cfg(name):
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_d4_by_hand():
    cfg = _cfg("mistral-7b-v0.3.d4")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336      # q o, k v, ffn
    assert flops.layer_matmul_params(cfg) == layer == 218_103_808
    assert flops.matmul_params(cfg) == 4 * layer + 4096 * 32768 == 1_006_632_960
    assert flops.total_params(cfg) == 1_006_632_960 + 32768 * 4096 + 9 * 4096
    attn = 3 * 4 * (2 * 2 * 32 * 128 * 2048 / 2)
    assert flops.train_flops_per_token(cfg, 2048) == 6 * 1_006_632_960 + attn
    assert round(flops.train_flops_per_token(cfg, 2048) / 1e9, 2) == 6.24


def test_yi_d16_by_hand():
    cfg = _cfg("yi-1.5-9b.d16")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert flops.layer_matmul_params(cfg) == layer == 173_015_040
    assert flops.serve_weight_bytes(cfg) == 2 * (16 * layer + 4096 * 64000)
    assert round(flops.serve_weight_bytes(cfg) / 1e9, 2) == 6.06
    assert flops.kv_bytes_per_token(cfg) == 2 * 16 * 4 * 128 * 2
    # one token at position 9 through the layers, its logits taken once
    one = 2 * 16 * layer + 16 * 4 * 32 * 128 * 10 + 2 * 4096 * 64000
    assert flops.serve_flops(cfg, [9], 1) == one


def test_flash_costs_and_roofline():
    f, b = flops.flash_fwd_cost(4, 2048, 32, 128)
    assert f == 4 * 4 * 32 * 2048 * 2048 * 128 / 2
    assert b == 4 * (4 * 2048 * 32 * 128 * 2) + 4 * 32 * 2048 * 4
    fb, _ = flops.flash_bwd_cost(4, 2048, 32, 128)
    assert fb == 2.5 * f
    t, bound = flops.roofline_seconds(f, b, peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and abs(t - f / 197e12) < 1e-12


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
