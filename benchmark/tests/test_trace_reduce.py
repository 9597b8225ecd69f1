"""lib/trace_reduce.py on synthetic intervals and on a small trace recorded
here (on the CPU: the shape of the reduction, never a device number)."""
import time

import pytest

from benchmark.lib import trace_reduce as tr


def test_union_gaps_and_idle_share():
    busy = tr.union([(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)])
    assert busy == [[0, 20], [30, 45]]
    assert tr.gaps_of(busy, 0, 60) == [(20, 30), (45, 60)]
    assert tr.gaps_of(busy, 10, 40) == [(20, 30)]
    ops = [(0, 10, "%a = f32[2] add(f32[2] %x)", ""), (5, 20, "%b = f32[2] add()", ""),
           (30, 45, '%k = f32[2] custom-call(), custom_call_target="tpu_custom_call"',
            "_flash_fwd_pallas")]
    spans = [(0, 60, tr.WINDOW_SPAN), (18, 31, "bench.call_step"), (44, 60, "bench.wait")]
    red = tr.reduce_events([ops], spans)
    assert red["window_s"] == pytest.approx(60e-9)
    assert red["busy_s"] == pytest.approx(35e-9)
    assert red["idle_share"] == pytest.approx(25 / 60)
    assert dict(map(tuple, red["idle_gaps"])) == pytest.approx(
        {"bench.call_step": 10e-9, "bench.wait": 15e-9})
    seconds, events = tr.op_seconds(red, "_flash_fwd_pallas")
    assert (seconds, events) == (pytest.approx(15e-9), 1)
    assert tr.op_seconds(red, "no_such_kernel") == (0, 0)
    assert "k custom-call f32[2] tpu_custom_call" in red["ops"]


def test_window_clips_and_devices_average():
    ops = [(0, 100, "%a = f32[2] add()", "")]
    red = tr.reduce_events([ops, []], [(50, 150, tr.WINDOW_SPAN)], n_devices=2)
    assert red["busy_s"] == pytest.approx(25e-9)          # (50 + 0) / 2 devices
    assert red["idle_gaps"][0][0] == "host, unattributed"


def test_short_names():
    line = ("%fusion.31 = (bf16[4096,32768]{1,0:T(8,128)(2,1)}, bf16[8]{0}) "
            "fusion(bf16[4096,32768]{1,0} %m), kind=kOutput, calls=%fc.3")
    assert tr.short_name(line) == "fusion.31 fusion bf16[4096,32768]+"
    assert tr.short_name("dot_general.1") == "dot_general.1"


def test_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call_step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.pause"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    red = tr.reduce_dir(str(tmp_path))
    assert red["n_ops"] >= 3 and 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] > 0.06
    assert any("dot" in name for name in red["ops"])
    assert red["idle_gaps"][0][0] == "bench.pause"
    assert "PLANE" in tr.describe(jax.profiler.ProfileData.from_file(
        tr.find_xplane(str(tmp_path))))
