"""Unified telemetry runtime: instrument semantics, registry snapshot
nesting, Prometheus exposition validity, kill switch, the /metrics HTTP
endpoint, the step timeline, and the cross-subsystem integration
(dispatch / fusion / checkpoint / serving counters all landing in ONE
snapshot)."""
from __future__ import annotations

import json
import re
import tempfile
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability.metrics import (
    Counter, Gauge, Histogram, Registry, DEFAULT_BUCKETS)


# ---------------------------------------------------------------------------
# instrument semantics (fresh private registries: no cross-test state)
# ---------------------------------------------------------------------------

class TestInstruments:
    def test_counter_unlabeled(self):
        r = Registry()
        c = r.counter("x.total", "help")
        assert c.value() == 0
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5

    def test_counter_labeled_cells_are_independent(self):
        c = Registry().counter("ops.total")
        c.inc(op="add")
        c.inc(2, op="mul")
        c.inc(op="add")
        c.inc()  # unlabeled cell is separate
        assert c.value(op="add") == 2
        assert c.value(op="mul") == 2
        assert c.value() == 1

    def test_counter_label_values_keep_python_type(self):
        c = Registry().counter("chain.length")
        c.inc(**{"len": 12})
        series = c.series()
        (key, v), = series.items()
        assert key == (("len", 12),) and v == 1
        assert isinstance(key[0][1], int)  # fusion view needs int back

    def test_gauge_set_inc_dec(self):
        g = Registry().gauge("depth")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6

    def test_gauge_pull_function(self):
        g = Registry().gauge("cache.size")
        g.set_function(lambda: 42)
        assert g.value() == 42
        # a dying pull fn degrades to 0, never raises at snapshot time
        g.set_function(lambda: 1 / 0)
        assert g.value() == 0

    def test_histogram_buckets_and_moments(self):
        h = Registry().histogram("lat", buckets=[0.001, 0.01, 0.1, 1.0])
        for v in (0.0005, 0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        d = h.value()
        assert d["count"] == 5
        assert d["min"] == pytest.approx(0.0005)
        assert d["max"] == pytest.approx(5.0)
        assert d["sum"] == pytest.approx(5.5555)
        # per-bucket (non-cumulative) counts: one value per bucket + +Inf
        assert d["buckets"] == {"0.001": 1, "0.01": 1, "0.1": 1,
                                "1": 1, "+Inf": 1}

    def test_histogram_default_buckets_log_spaced(self):
        assert DEFAULT_BUCKETS[0] == pytest.approx(1e-6)
        assert DEFAULT_BUCKETS[-1] == pytest.approx(100.0)
        ratios = {round(b2 / b1, 3) for b1, b2 in
                  zip(DEFAULT_BUCKETS, DEFAULT_BUCKETS[1:])}
        assert ratios == {round(10 ** 0.5, 3)}  # fixed half-decade steps

    def test_histogram_labeled(self):
        h = Registry().histogram("phase.s", buckets=[1.0])
        h.observe(0.5, phase="fwd")
        h.observe(2.0, phase="bwd")
        assert h.value(phase="fwd")["count"] == 1
        assert h.value(phase="bwd")["max"] == 2.0
        assert h.value()["count"] == 0  # unlabeled cell untouched

    def test_get_or_create_idempotent_and_type_checked(self):
        r = Registry()
        a = r.counter("x")
        assert r.counter("x") is a
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_scope_prefixes(self):
        r = Registry()
        s = r.scope("serving")
        c = s.counter("admitted_total")
        assert c.name == "serving.admitted_total"
        assert r.get("serving.admitted_total") is c
        assert s.scope("sub").gauge("g").name == "serving.sub.g"


# ---------------------------------------------------------------------------
# kill switch
# ---------------------------------------------------------------------------

class TestKillSwitch:
    def test_disabled_instruments_do_not_move(self):
        r = Registry()
        c, g, h = r.counter("c"), r.gauge("g"), r.histogram("h")
        paddle.set_flags({"FLAGS_metrics": 0})
        try:
            c.inc(100)
            c.inc(op="x")
            g.set(9)
            h.observe(1.0)
            assert c.value() == 0 and c.value(op="x") == 0
            assert g.value() == 0
            assert h.value()["count"] == 0
        finally:
            paddle.set_flags({"FLAGS_metrics": 1})
        c.inc()
        assert c.value() == 1  # re-enabled

    def test_enabled_reflects_flag(self):
        assert obs.enabled()
        paddle.set_flags({"FLAGS_metrics": 0})
        try:
            assert not obs.enabled()
        finally:
            paddle.set_flags({"FLAGS_metrics": 1})


# ---------------------------------------------------------------------------
# snapshot nesting + collectors
# ---------------------------------------------------------------------------

class TestSnapshot:
    def test_nested_by_dotted_name(self):
        r = Registry()
        r.counter("serving.admitted_total").inc(3)
        r.gauge("serving.queue_depth").set(2)
        r.counter("a.b.c_total").inc()
        snap = r.snapshot()
        assert snap["serving"]["admitted_total"] == 3
        assert snap["serving"]["queue_depth"] == 2
        assert snap["a"]["b"]["c_total"] == 1

    def test_labeled_series_nest_as_dicts(self):
        r = Registry()
        c = r.counter("ops.by_name")
        c.inc(op="add")
        c.inc(2, op="mul")
        assert r.snapshot()["ops"]["by_name"] == {"add": 1, "mul": 2}

    def test_collector_merged_at_snapshot_time(self):
        r = Registry()
        calls = []

        def collect():
            calls.append(1)
            return {"faults.injected_total": {"store.add": 2},
                    "faults.scalar": 7}

        r.register_collector("faults", collect)
        assert not calls  # pull-based: nothing until snapshot
        snap = r.snapshot()
        assert snap["faults"]["injected_total"] == {"store.add": 2}
        assert snap["faults"]["scalar"] == 7

    def test_broken_collector_is_skipped(self):
        r = Registry()
        r.counter("ok.total").inc()
        r.register_collector("bad", lambda: 1 / 0)
        assert r.snapshot()["ok"]["total"] == 1

    def test_snapshot_is_json_serializable(self):
        r = Registry()
        r.histogram("h").observe(0.01, phase="fwd")
        r.counter("c").inc(**{"len": 3})
        json.dumps(r.snapshot())


# ---------------------------------------------------------------------------
# prometheus exposition golden checks
# ---------------------------------------------------------------------------

_LABEL_VAL = r'"(?:\\.|[^"\\])*"'  # escaped \" \\ \n stay in-line
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                      # metric name
    rf"(\{{[a-zA-Z_][a-zA-Z0-9_]*={_LABEL_VAL}"       # first label
    rf"(,[a-zA-Z_][a-zA-Z0-9_]*={_LABEL_VAL})*\}})?"  # more labels
    r" [-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|\+Inf|NaN)$")


def _parse_exposition(text):
    """Minimal exposition-format checker: every line is a HELP/TYPE
    comment or a valid sample; returns {metric_name: [(labels, value)]}."""
    samples = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            assert len(parts) >= 3, line
            continue
        assert _SAMPLE_RE.match(line), f"invalid exposition line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        labels = ""
        if "{" in line:
            labels = line[line.index("{"):line.rindex("}") + 1]
        samples.setdefault(name, []).append(
            (labels, float(line.rsplit(" ", 1)[1])))
    return samples


class TestPrometheus:
    def _registry(self):
        r = Registry()
        c = r.counter("serving.admitted_total", "Requests admitted")
        c.inc(3)
        r.gauge("serving.queue_depth", "Queued").set(2)
        h = r.histogram("rt.seconds", "latency", buckets=[0.01, 0.1, 1.0])
        h.observe(0.005)
        h.observe(0.5)
        h.observe(50.0)
        lc = r.counter("ops.total")
        lc.inc(op="add")
        lc.inc(op='we"ird\nname')  # must be escaped, stay one line
        return r

    def test_every_line_parses(self):
        _parse_exposition(self._registry().render_prometheus())

    def test_names_sanitized_and_typed(self):
        text = self._registry().render_prometheus()
        assert "# TYPE serving_admitted_total counter" in text
        assert "# TYPE serving_queue_depth gauge" in text
        assert "# TYPE rt_seconds histogram" in text
        assert "# HELP serving_admitted_total Requests admitted" in text
        assert "serving_admitted_total 3" in text
        assert "." not in [ln.split(" ")[0] for ln in text.splitlines()
                           if ln and not ln.startswith("#")][0]

    def test_histogram_invariants(self):
        samples = _parse_exposition(
            self._registry().render_prometheus())
        buckets = samples["rt_seconds_bucket"]
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), "cumulative buckets monotone"
        inf = [v for lbl, v in buckets if 'le="+Inf"' in lbl]
        assert inf == [samples["rt_seconds_count"][0][1]] == [3.0]
        assert samples["rt_seconds_sum"][0][1] == pytest.approx(50.505)

    def test_label_escaping(self):
        text = self._registry().render_prometheus()
        line = next(ln for ln in text.splitlines() if "we" in ln)
        assert '\\"' in line and "\\n" in line

    def test_default_registry_renders(self):
        _parse_exposition(obs.render_prometheus())


class TestPrometheusEdgeCases:
    """Exposition corners the mini-parser didn't pin before ISSUE 8:
    hostile label values and the histogram +Inf/_count invariant
    across labeled, multi-label and empty cells."""

    def test_backslash_and_trailing_backslash_label_values(self):
        r = Registry()
        c = r.counter("edge.total")
        c.inc(path="C:\\tmp\\x")       # interior backslashes
        c.inc(path="trailing\\")       # a trailing backslash must not
        c.inc(path='quote"inside')     # escape the closing quote
        c.inc(path="multi\nline\\mix\"")
        text = r.render_prometheus()
        samples = _parse_exposition(text)  # every line stays valid
        assert len(samples["edge_total"]) == 4
        # escaping is per spec: \ -> \\, newline -> \n, " -> \"
        assert 'path="C:\\\\tmp\\\\x"' in text
        assert 'path="trailing\\\\"' in text
        assert 'path="quote\\"inside"' in text
        assert 'path="multi\\nline\\\\mix\\""' in text
        assert "\n\n" not in text  # no raw newline leaked into a line

    def test_label_roundtrip_distinct_cells(self):
        """Two values that would collide if escaping were sloppy
        ('a\\' + 'b' vs 'a' + '\\b') must render as distinct series."""
        r = Registry()
        c = r.counter("collide.total")
        c.inc(2, k="a\\", j="b")
        c.inc(3, k="a", j="\\b")
        samples = _parse_exposition(r.render_prometheus())
        vals = sorted(v for _, v in samples["collide_total"])
        assert vals == [2.0, 3.0]
        labels = {lbl for lbl, _ in samples["collide_total"]}
        assert len(labels) == 2

    def test_labeled_histogram_inf_bucket_equals_count(self):
        """For EVERY cell of a labeled histogram: the cumulative +Inf
        bucket == its _count, and bucket counts are monotone within
        that cell (the invariant scrapers rely on for quantiles)."""
        r = Registry()
        h = r.histogram("lab.seconds", buckets=[0.01, 1.0])
        for v, phase in [(0.005, "fwd"), (0.5, "fwd"), (50.0, "fwd"),
                         (2.0, "bwd")]:
            h.observe(v, phase=phase)
        samples = _parse_exposition(r.render_prometheus())
        counts = {lbl: v for lbl, v in samples["lab_seconds_count"]}
        for phase, expect in [("fwd", 3.0), ("bwd", 1.0)]:
            cell = [(lbl, v) for lbl, v in samples["lab_seconds_bucket"]
                    if f'phase="{phase}"' in lbl]
            vals = [v for _, v in cell]
            assert vals == sorted(vals), "per-cell buckets monotone"
            inf = [v for lbl, v in cell if 'le="+Inf"' in lbl]
            assert inf == [expect]
            (count_lbl,) = [lbl for lbl in counts
                            if f'phase="{phase}"' in lbl]
            assert counts[count_lbl] == expect
            # every bucket line carries BOTH the cell label and le
            assert all('le="' in lbl for lbl, _ in cell)

    def test_empty_histogram_renders_consistent_zero_series(self):
        """A registered-but-never-observed histogram still exposes a
        full bucket ladder with +Inf == _count == 0 (scrapers must see
        the series exist, not a hole)."""
        r = Registry()
        r.histogram("never.seconds", buckets=[0.1, 1.0])
        samples = _parse_exposition(r.render_prometheus())
        assert samples["never_seconds_count"] == [("", 0.0)]
        assert samples["never_seconds_sum"] == [("", 0.0)]
        buckets = samples["never_seconds_bucket"]
        assert [v for _, v in buckets] == [0.0, 0.0, 0.0]
        assert any('le="+Inf"' in lbl for lbl, _ in buckets)


# ---------------------------------------------------------------------------
# /metrics HTTP endpoint
# ---------------------------------------------------------------------------

class TestHTTPEndpoint:
    def test_round_trip(self):
        r = Registry()
        r.counter("demo.hits_total", "demo").inc(5)
        from paddle_tpu.observability.http import start_metrics_server
        with start_metrics_server(registry=r) as srv:
            assert srv.port > 0
            body = urllib.request.urlopen(srv.url, timeout=10).read()
            text = body.decode()
            _parse_exposition(text)
            assert "demo_hits_total 5" in text
            jbody = urllib.request.urlopen(
                srv.url + ".json", timeout=10).read()
            assert json.loads(jbody)["demo"]["hits_total"] == 5
            code = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10).status
            assert code == 200
        with pytest.raises(Exception):
            urllib.request.urlopen(srv.url, timeout=2)

    def test_404(self):
        from paddle_tpu.observability.http import start_metrics_server
        with start_metrics_server(registry=Registry()) as srv:
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=10)


# ---------------------------------------------------------------------------
# step timeline
# ---------------------------------------------------------------------------

class TestStepTimer:
    def test_phases_and_step_events(self):
        r = Registry()
        t = obs.StepTimer("traintest", registry=r)
        for _ in range(2):
            with t.phase("forward"):
                time.sleep(0.002)
            with t.phase("optimizer"):
                pass
            phases = t.step()
        assert set(phases) == {"forward", "optimizer"}
        assert phases["forward"] >= 0.002
        snap = r.snapshot()
        assert snap["step"]["steps_total"] == 2
        assert snap["step"]["step_seconds"]["count"] == 2
        assert snap["step"]["phase_seconds"]["forward"]["count"] == 2
        evs = t.chrome_events()
        assert len(evs) == 2
        assert evs[0]["ph"] == "C"
        assert evs[0]["name"] == "traintest.step_phases_ms"
        assert evs[0]["args"]["forward"] >= 2.0  # ms
        # module-level aggregation feeds export_chrome_tracing
        from paddle_tpu.observability import timeline
        assert any(e in timeline.chrome_events() for e in evs)

    def test_repeated_phase_accumulates_within_step(self):
        t = obs.StepTimer("acc", registry=Registry())
        with t.phase("data"):
            pass
        with t.phase("data"):
            pass
        phases = t.step()
        assert list(phases) == ["data"]


# ---------------------------------------------------------------------------
# cross-subsystem integration: one snapshot carries everything
# ---------------------------------------------------------------------------

class TestIntegration:
    def test_dispatch_metrics_move(self):
        snap0 = obs.snapshot()["dispatch"]
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        (x + x).numpy()
        snap1 = obs.snapshot()["dispatch"]
        assert snap1["ops_total"] > snap0["ops_total"]
        assert sum(snap1["ops_dispatched_total"].values()) >= \
            sum(snap0.get("ops_dispatched_total", {}).values())

    def test_fusion_stats_is_view_of_registry(self):
        from paddle_tpu.core import fusion
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        paddle.add(paddle.multiply(x, 2.0), 1.0).numpy()
        s = fusion.stats()
        snap = obs.snapshot()["fusion"]
        assert s["chains_flushed"] == snap["chains_flushed_total"]
        assert s["cache_hits"] == snap["cache_hits_total"]
        assert s["flush_reasons"] == snap.get("flushes_total",
                                              s["flush_reasons"])
        # chain-length keys come back as ints through the view
        assert all(isinstance(k, int) for k in s["chain_length_hist"])

    def test_checkpoint_metrics_move(self):
        from paddle_tpu.framework.checkpoint import CheckpointManager
        before = obs.snapshot()["checkpoint"]
        with tempfile.TemporaryDirectory() as d:
            m = CheckpointManager(d, keep_n=1)
            m.save({"w": paddle.to_tensor(np.ones(4, np.float32))}, step=0)
            m.restore()
        after = obs.snapshot()["checkpoint"]
        assert after["saves_total"] == before["saves_total"] + 1
        assert after["bytes_written_total"] > before["bytes_written_total"]
        assert after["save_seconds"]["count"] == \
            before["save_seconds"]["count"] + 1
        assert after["loads_total"] == before["loads_total"] + 1

    def test_serving_metrics_and_endpoint(self):
        from paddle_tpu.serving import GenerationServer
        from tests.test_flight import FakeEngine  # jax-free, duck-typed
        before = obs.snapshot()["serving"]
        srv = GenerationServer(FakeEngine())
        try:
            ep = srv.metrics_endpoint()
            assert srv.metrics_endpoint() is ep  # idempotent
            out = srv.generate([1, 2, 3], max_new_tokens=3, timeout=30)
            assert out[0] == 7 and len(out) == 3
            after = obs.snapshot()["serving"]
            assert after["admitted_total"] == before["admitted_total"] + 1
            assert after["tokens_total"] >= before["tokens_total"] + 3
            assert after["request_seconds"]["count"] > \
                before["request_seconds"]["count"]
            assert after["token_seconds"]["count"] > \
                before["token_seconds"]["count"]
            body = urllib.request.urlopen(ep.url, timeout=10).read()
            assert b"serving_admitted_total" in body
            # idle server: gauges must read 0, not the last mid-step
            # values (a finished request is not "in flight")
            deadline = time.monotonic() + 10
            g_inflight = obs.default_registry().get("serving.in_flight")
            g_queue = obs.default_registry().get("serving.queue_depth")
            while time.monotonic() < deadline and (
                    g_inflight.value() or g_queue.value()):
                time.sleep(0.01)
            assert g_inflight.value() == 0
            assert g_queue.value() == 0
        finally:
            srv.shutdown()
        assert srv._metrics_server is None  # shutdown closes the endpoint

    def test_fault_injection_lands_in_snapshot(self):
        from paddle_tpu.utils import fault_injection as fi
        site = "obs.test.site"
        before = obs.snapshot().get("faults", {}).get(
            "injected_total", {}).get(site, 0)
        with fi.injected(site):
            with pytest.raises(fi.InjectedFault):
                fi.fire(site)
        got = obs.snapshot()["faults"]["injected_total"][site]
        assert got == before + 1
        assert fi.stats()[site] >= 1  # legacy surface intact

    def test_store_retry_counter(self):
        # the counter instrument exists and moves when incremented the
        # way TCPStore._call does (the full retry loop is exercised by
        # test_fault_tolerance against a live store server)
        from paddle_tpu.distributed import store as store_mod
        v0 = store_mod._M_retries.value(op="add")
        store_mod._M_retries.inc(op="add")
        assert store_mod._M_retries.value(op="add") == v0 + 1

    def test_watchdog_span_lands_in_registry(self):
        from paddle_tpu.distributed.watchdog import Watchdog, _M_span_s
        wd = Watchdog(timeout=60.0)
        c0 = _M_span_s.value(name="unit_span")["count"]
        with wd.span("unit_span"):
            pass
        assert _M_span_s.value(name="unit_span")["count"] == c0 + 1
