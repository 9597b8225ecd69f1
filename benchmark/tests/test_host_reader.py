"""readers/host.py: device idle time under the program's `host.gc` spans on
hand-built intervals (a span on another thread, spans that overlap, spans
across the window's edge, two devices), what a trace without such spans (a
parent commit's) yields, and the spans read back from a real trace."""
import gc

import jax
import pytest

from benchmark.lib import trace_reduce as tr
from benchmark.readers import host as readers

WINDOW = (0, 1000, tr.WINDOW_SPAN)
OPS = [[(0, 300, "%a = f32[2] add()", ""), (600, 1000, "%b = f32[2] add()", "")]]


def _obs(monkeypatch, ops, spans, n_devices=1):
    red = readers.reduce_host(ops, [WINDOW], spans, n_devices)
    monkeypatch.setattr(readers, "_load", lambda obs: red)
    return {"trace": {"idle_share": None}, "cell": {"chips": n_devices}}


def test_idle_under_a_collection_on_any_thread(monkeypatch):
    """The loop waits in a fetch while another thread collects: the gap the
    collection covers counts, whichever line of the trace it is on."""
    obs = _obs(monkeypatch, OPS, [(350, 450, "host.gc")])
    assert readers.idle_under(obs, "host.gc") == pytest.approx(10.0)
    assert readers.spans_ms(obs, "host.gc") == pytest.approx(100e-6)


def test_overlapping_spans_count_once_and_busy_time_not_at_all(monkeypatch):
    spans = [(350, 450, "host.gc"), (400, 500, "host.gc"),
             (250, 320, "host.gc"), (700, 800, "host.gc")]
    obs = _obs(monkeypatch, OPS, spans)
    # 350-500 once, 300-320 of the span over busy time, none of 700-800
    assert readers.idle_under(obs, "host.gc") == pytest.approx(17.0)
    idle = 100.0 * tr.reduce_events(OPS, [WINDOW])["idle_share"]
    assert readers.idle_under(obs, "host.gc") <= idle
    # the time sums every span, whether or not the device waited
    assert readers.spans_ms(obs, "host.gc") == pytest.approx(370e-6)


def test_spans_are_clipped_to_the_window(monkeypatch):
    obs = _obs(monkeypatch, OPS, [(-50, 20, "host.gc"), (550, 1200, "host.gc")])
    assert readers.idle_under(obs, "host.gc") == pytest.approx(5.0)
    assert readers.spans_ms(obs, "host.gc") == pytest.approx((20 + 450) * 1e-6)
    outside = _obs(monkeypatch, OPS, [(1100, 1200, "host.gc")])
    assert readers.idle_under(outside, "host.gc") == 0.0
    assert readers.spans_ms(outside, "host.gc") == 0.0


def test_two_devices_average(monkeypatch):
    ops = [[(0, 1000, "%a = f32[2] add()", "")], []]
    obs = _obs(monkeypatch, ops, [(0, 500, "host.gc")], n_devices=2)
    assert readers.idle_under(obs, "host.gc") == pytest.approx(25.0)


def test_without_such_spans_nothing_is_read(monkeypatch):
    """What a parent commit's trace gives: None from both readers."""
    assert readers.reduce_host(OPS, [WINDOW], []) is None
    assert readers.reduce_host(OPS, [], [(0, 10, "host.gc")]) is None
    for spans in ([], [(350, 450, "host.other")]):
        obs = _obs(monkeypatch, OPS, spans)
        assert readers.idle_under(obs, "host.gc") is None
        assert readers.spans_ms(obs, "host.gc") is None
    monkeypatch.undo()
    assert readers._load({"trace": None, "cell": {"chips": 1}}) is None


def test_the_spans_are_read_from_a_real_trace(tmp_path):
    import paddle_tpu  # noqa: F401  (installs the collector's hook)
    from paddle_tpu.profiler import RecordEvent
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    gc.disable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with RecordEvent("bench.window"):
            gc.collect(2)
    finally:
        jax.profiler.stop_trace()
        gc.enable()
    pd = jax.profiler.ProfileData.from_file(tr.find_xplane(str(tmp_path)))
    spans = readers.read_host_spans(pd)
    assert [name for *_, name in spans] == ["host.gc"]
    _, bench_spans = tr.read_planes(pd)
    red = readers.reduce_host([[]], bench_spans, spans)
    (s, e, _), (lo, hi) = spans[0], red["window_ns"]
    assert lo <= s and e <= hi
