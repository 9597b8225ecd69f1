"""The program's own spans: one primitive, `profiler.RecordEvent`, writes the
native host plane and, while a `jax.profiler` trace runs, the `/host:CPU`
plane of the same `.xplane.pb` as the device operations; every Python garbage
collection is such a span too (`host.gc`, `observability/host.py`).

A module-scoped traced run of a tiny paged server (a few requests, a weight
swap, an idle park) is read back with `jax.profiler.ProfileData`; the tests
look at names, attributes, nesting, the counts on the spans against the
flight recorder's own trail, and that tracing changes no served token.
"""
import gc
import glob
import json
import math
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     LlamaPretrainingCriterion)
from paddle_tpu.observability import clock, flight, host, timeline
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.profiler import Profiler, RecordEvent
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, use_flash_attention=False)
CHUNK = 8
# prompt lengths: several chunks, one sub-chunk bucket, a prefix of the first
PROMPTS = (list(range(1, 30)), [5, 9, 11], list(range(1, 20)),
           list(range(20, 41)))
NEW = 6


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


def _engine(model):
    return PagedLlamaDecodeEngine(model, max_slots=2, max_seq=64,
                                  block_size=8, prefill_chunk=CHUNK)


def _serve(srv, prompts=PROMPTS):
    reqs = [srv.submit(np.asarray(p, np.int32), NEW) for p in prompts]
    for r in reqs:
        assert r["done"].wait(120) and r["error"] is None, r["error"]
    return reqs


def _host_spans(trace_dir, prefixes=("serving.", "train.", "jit.", "t26.")):
    """(start, end, name, stats, line) of the program's spans in a trace."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats), (plane.name, i)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _trace(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One traced run: four requests over two slots (one hits the first's
    prefix), then a weight swap with a request in flight, then a request
    that finds the loop parked."""
    eng = _engine(model)
    srv = GenerationServer(eng)
    _serve(srv, [list(range(40, 50))])          # compile outside the trace
    trace_dir = tmp_path_factory.mktemp("trace26")
    _trace(trace_dir)
    try:
        reqs = _serve(srv)
        busy = srv.submit(np.asarray(PROMPTS[0], np.int32), 24)
        srv.swap_weights(model.state_dict())
        assert busy["done"].wait(120)
        time.sleep(0.05)                        # the loop parks on its queue
        late = _serve(srv, [[3, 1, 4, 1, 5]])
    finally:
        jax.profiler.stop_trace()
    trails = {r["trace_id"]: srv.trace(r) for r in reqs + late}
    srv.shutdown()
    return {"spans": _host_spans(str(trace_dir)), "reqs": reqs + late,
            "trails": trails, "outs": [list(r["out"]) for r in reqs],
            "eng": eng}


def _named(traced, name):
    return [s for s in traced["spans"] if s[2] == name]


SERVING_SPANS = {
    "serving.iter": {"step", "active", "prefilling", "waiting"},
    "serving.admit": {"admitted"},
    "serving.prefill": {"trace_id", "slot", "start", "tokens", "bucket"},
    "serving.prefill.enqueue": set(),
    "serving.prefill.fetch": {"ready"},
    "serving.decode": {"step", "rows", "live_tokens", "max_ctx",
                       "walk_tokens", "spec"},
    "serving.decode.prepare": set(),
    "serving.decode.enqueue": set(),
    "serving.decode.fetch": {"ready"},
    "serving.commit": {"tokens"},
    "serving.sweep": set(),
    "serving.swap": set(),
    "serving.idle": set(),
}


@pytest.mark.parametrize("name", sorted(SERVING_SPANS))
def test_a_traced_run_has_every_serving_span_with_its_attributes(traced, name):
    spans = _named(traced, name)
    assert spans, f"no {name} span in the trace"
    for _, _, _, stats, _ in spans:
        # a launch enqueued while the last was not fetched says so, and
        # so does a fetch far past its kind's mean (a stall)
        assert set(stats) - {"ahead", "stall"} == SERVING_SPANS[name], \
            (name, stats)
        assert stats.get("ahead", 1) == 1 and \
            ("ahead" not in stats or name == "serving.decode")
        assert stats.get("stall", 1) == 1 and \
            ("stall" not in stats or name.endswith(".fetch"))


def test_a_fetch_span_says_whether_the_device_was_done(traced):
    """`ready` is read as the fetch begins: 1 where the device had finished
    the launch before the host came for it, so the whole fetch was host
    time."""
    for name in ("serving.decode.fetch", "serving.prefill.fetch"):
        assert {s[3]["ready"] for s in _named(traced, name)} <= {0, 1}


def test_most_decode_launches_go_out_ahead_of_the_last_ones_fetch(traced):
    """The loop keeps one launch in flight: a `serving.decode` span enqueues
    launch n+1 and its `.fetch` waits for launch n, so `ahead` is on every
    span but the first after the loop had nothing in flight (its start, a
    weight swap, a batch that ended)."""
    spans = _named(traced, "serving.decode")
    ahead = [s for s in spans if s[3].get("ahead")]
    assert len(ahead) >= len(spans) // 2
    fetches = _named(traced, "serving.decode.fetch")
    inside = [f for f in fetches if any(
        d[0] <= f[0] and f[1] <= d[1] for d in ahead)]
    assert len(inside) == len(ahead)


@pytest.mark.parametrize("child,parent", [
    # a fetch waits for the launch in flight: under the `serving.decode`
    # that enqueues the next launch, or with none to enqueue under the pass
    ("serving.decode.fetch", "serving.iter"),
    ("serving.decode.prepare", "serving.decode"),
    ("serving.decode.enqueue", "serving.decode"),
    ("serving.decode", "serving.iter"),
    ("serving.prefill.enqueue", "serving.prefill"),
    ("serving.prefill.fetch", "serving.iter"),
    ("serving.prefill", "serving.iter"),
    ("serving.commit", "serving.iter"),
    ("serving.sweep", "serving.iter"),
    ("serving.swap", "serving.iter"),
    ("serving.idle", "serving.iter"),
])
def test_spans_nest_by_time_on_the_loops_thread(traced, child, parent):
    parents = _named(traced, parent)
    kids = _named(traced, child)
    assert kids
    # the first spans of a trace may have a parent that opened before it
    inside = [k for k in kids if any(
        p[0] <= k[0] and k[1] <= p[1] and p[4] == k[4] for p in parents)]
    assert len(inside) >= len(kids) - 1, (child, parent)
    assert inside


def test_iterations_are_disjoint_roots(traced):
    iters = _named(traced, "serving.iter")
    assert len(iters) > 10
    for a, b in zip(iters, iters[1:]):
        assert a[1] <= b[0]
    steps = [s[3]["step"] for s in iters]
    assert steps == sorted(steps)


def test_prefill_chunk_events_count_the_turns_and_precede_prefilled(traced):
    for req in traced["reqs"]:
        trail = traced["trails"][req["trace_id"]]
        names = [e["name"] for e in trail]
        admitted = next(e for e in trail if e["name"] == "admitted")
        hit = admitted["attrs"]["prefix_hit"]
        n = int(req["prompt"].shape[0])
        chunks = [e for e in trail if e["name"] == "prefill_chunk"]
        assert len(chunks) == math.ceil((n - hit) / CHUNK), (n, hit, names)
        assert names.index("prefilled") > max(
            i for i, x in enumerate(names) if x == "prefill_chunk")
        at = hit
        for e in chunks:        # the turns tile the unmatched prompt
            a = e["attrs"]
            assert set(a) == {"slot", "start", "tokens", "bucket"}
            assert a["start"] == at and 0 < a["tokens"] <= a["bucket"] <= CHUNK
            at += a["tokens"]
        assert at == n


def test_one_request_hit_the_prefix_tree(traced):
    hits = [next(e for e in t if e["name"] == "admitted")["attrs"]["prefix_hit"]
            for t in traced["trails"].values()]
    assert max(hits) > 0 and min(hits) == 0


def test_prefill_spans_share_trace_id_and_counts_with_the_flight_events(traced):
    spans = _named(traced, "serving.prefill")
    ids = {r["trace_id"] for r in traced["reqs"]}
    by = {}
    for _, _, _, st, _ in spans:
        by.setdefault(st["trace_id"], []).append(
            (st["slot"], st["start"], st["tokens"], st["bucket"]))
    assert ids <= set(by)
    for tid in ids:
        events = [(e["attrs"]["slot"], e["attrs"]["start"], e["attrs"]["tokens"],
                   e["attrs"]["bucket"]) for e in traced["trails"][tid]
                  if e["name"] == "prefill_chunk"]
        assert by[tid] == events


def test_live_tokens_is_the_sum_of_pos_plus_one_over_the_active_slots(traced):
    """A request with prompt n that holds m tokens after a step sat at
    pos = n + m - 2 before it, so the launch read n + m - 1 of its KV."""
    want = {}
    for req in traced["reqs"]:
        n = int(req["prompt"].shape[0])
        for e in traced["trails"][req["trace_id"]]:
            if e["name"] == "decode":
                rows, live, longest = want.get(e["attrs"]["step"], (0, 0, 0))
                ctx = n + e["attrs"]["tokens"] - 1
                want[e["attrs"]["step"]] = (rows + 1, live + ctx,
                                            max(longest, ctx))
    seen = 0
    for _, _, _, st, _ in _named(traced, "serving.decode"):
        if st["step"] in want and st["rows"] == want[st["step"]][0]:
            assert (st["live_tokens"], st["max_ctx"]) == want[st["step"]][1:]
            assert st["spec"] == 0
            seen += 1
    assert seen >= 8


def test_launch_counts_reads_the_engines_slot_state(model):
    srv = GenerationServer(_engine(model))
    try:
        eng = srv.engine
        eng.pos[:] = (11, 30)
        eng.active[:] = (True, True)
        # the table is 8 blocks of 8 tokens: one group of the kernel's
        assert eng.walk_group_tokens() == 64
        assert srv._launch_counts() == {"rows": 2, "live_tokens": 43,
                                        "max_ctx": 31, "walk_tokens": 128}
        eng.active[:] = (False, True)
        assert srv._launch_counts() == {"rows": 1, "live_tokens": 31,
                                        "max_ctx": 31, "walk_tokens": 64}
        eng.active[:] = False
        assert srv._launch_counts() == {"rows": 0, "live_tokens": 0,
                                        "max_ctx": 0, "walk_tokens": 0}
        eng.pos[:] = 0
    finally:
        srv.shutdown()


def _round_up(n, to):
    return -(-n // to) * to


def test_walk_tokens_rounds_each_slot_up_to_the_kernels_group(model):
    """`walk_tokens` is what the paged kernel walks for a launch: every
    active slot's `pos + 1` rounded up to the group of blocks the kernel
    chose for the engine's shapes, so it lies between what is live and
    what walking every slot to the longest context would cost."""
    from paddle_tpu.ops.pallas import paged_attention as pk
    eng = PagedLlamaDecodeEngine(model, max_slots=3, max_seq=2048,
                                 block_size=16, prefill_chunk=CHUNK)
    srv = GenerationServer(eng)
    try:
        group = eng.walk_group_tokens()
        assert group == pk.group_tokens(
            16, CFG["num_key_value_heads"] * eng.head_dim,
            eng.kvs["k"][0].dtype, 1, eng.n_rep, 2048 // 16) == 512
        eng.pos[:] = (11, 600, 1023)
        eng.active[:] = (True, True, False)
        c = srv._launch_counts()
        assert c == {"rows": 2, "live_tokens": 613, "max_ctx": 601,
                     "walk_tokens": 512 + 1024}
        assert (c["live_tokens"] < c["walk_tokens"]
                < c["rows"] * _round_up(c["max_ctx"], group))
        eng.active[:] = True
        assert srv._launch_counts()["walk_tokens"] == 512 + 1024 + 1024
        eng.active[:] = False
        eng.pos[:] = 0
    finally:
        srv.shutdown()


def test_decode_spans_carry_walk_tokens_between_live_and_the_old_walk(traced):
    group = traced["eng"].walk_group_tokens()
    spans = _named(traced, "serving.decode")
    assert len(spans) >= 8
    for _, _, _, st, _ in spans:
        assert st["live_tokens"] <= st["walk_tokens"] \
            <= st["rows"] * _round_up(st["max_ctx"], group), st
        assert st["walk_tokens"] % group == 0


def test_commit_spans_count_the_tokens_delivered(traced):
    by_step = {}
    for req in traced["reqs"]:
        for e in traced["trails"][req["trace_id"]]:
            if e["name"] == "decode":
                by_step[e["attrs"]["step"]] = by_step.get(e["attrs"]["step"], 0) + 1
    commits = _named(traced, "serving.commit")
    decodes = _named(traced, "serving.decode")
    assert len(commits) == len(decodes)
    for c, d in zip(commits, decodes):
        if d[3]["step"] in by_step and d[3]["rows"] == by_step[d[3]["step"]]:
            assert c[3]["tokens"] == by_step[d[3]["step"]]


def test_served_tokens_are_the_same_with_and_without_a_trace(model, traced):
    srv = GenerationServer(_engine(model))
    try:
        plain = [list(r["out"]) for r in _serve(srv)]
    finally:
        srv.shutdown()
    assert plain == traced["outs"]
    assert all(len(o) == NEW for o in plain)


# -- the primitive ---------------------------------------------------------

def test_record_event_writes_the_xplane_with_attributes_and_late_counts(tmp_path):
    _trace(tmp_path)
    try:
        with RecordEvent("t26.outer", step=7, name="x") as span:
            with RecordEvent("t26.inner"):
                time.sleep(0.001)
            span.set(found=3)
    finally:
        jax.profiler.stop_trace()
    outer, inner = _host_spans(str(tmp_path))
    assert (outer[2], inner[2]) == ("t26.outer", "t26.inner")
    assert outer[3] == {"step": 7, "name": "x", "found": 3}
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


def test_record_event_nests_lifo_in_both_planes_at_once(tmp_path):
    ev = RecordEvent("t26.lifo")
    _trace(tmp_path / "x")
    try:
        with Profiler():
            ev.begin()
            time.sleep(0.002)
            ev.begin()
            time.sleep(0.001)
            ev.end()
            ev.end()
            ev.end()            # unbalanced: harmless
            out = str(tmp_path / "native.json")
            profiler.export_chrome_tracing(out)
    finally:
        jax.profiler.stop_trace()
    native = [e for e in json.load(open(out))["traceEvents"]
              if e["name"] == "t26.lifo"]
    xplane = _host_spans(str(tmp_path / "x"))
    assert len(native) == len(xplane) == 2
    durs = sorted(float(e["dur"]) for e in native)
    assert durs[0] < durs[1] and durs[1] >= 3000
    (a0, a1, *_), (b0, b1, *_) = xplane
    assert a0 <= b0 and b1 <= a1


# -- the host's own pauses ---------------------------------------------------

def _gc_counts():
    """Collections and their seconds by generation, as the registry shows
    them."""
    snap = obs_metrics.snapshot()["host"]
    return ([snap["gc_collections_total"][g] for g in range(3)],
            [snap["gc_seconds_total"][g] for g in range(3)])


def test_a_collection_is_a_span_on_the_thread_that_collected(tmp_path):
    """`gc.collect(2)` under a trace: one `host.gc` span, generation 2, inside
    the span that called it on its own line; the counts grow by generation
    (automatic collections are held off meanwhile, so it is the only one)."""
    n0, s0 = _gc_counts()
    gc.disable()
    _trace(tmp_path)
    try:
        with RecordEvent("t37.outer"):
            gc.collect(2)
    finally:
        jax.profiler.stop_trace()
        gc.enable()
    spans = _host_spans(str(tmp_path), prefixes=("t37.", "host."))
    (outer,) = [x for x in spans if x[2] == "t37.outer"]
    (coll,) = [x for x in spans if x[2] == "host.gc"]
    assert coll[3]["generation"] == 2
    assert {"collected", "uncollectable"} <= set(coll[3])
    assert outer[0] <= coll[0] and coll[1] <= outer[1] and coll[4] == outer[4]
    n1, s1 = _gc_counts()
    assert n1 == [n0[0], n0[1], n0[2] + 1]
    assert s1[2] > s0[2] and s1[:2] == s0[:2]
    assert host.gc_stats()[2][:2] == [n1[2], round(s1[2], 6)]
    assert host.gc_stats()[2][2] > 0


def test_with_metrics_off_a_collection_leaves_neither_span_nor_count(tmp_path):
    n0, s0 = _gc_counts()
    gc.disable()
    paddle.set_flags({"FLAGS_metrics": 0})
    _trace(tmp_path)
    try:
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
        paddle.set_flags({"FLAGS_metrics": 1})
        gc.enable()
    assert _host_spans(str(tmp_path), prefixes=("host.",)) == []
    assert _gc_counts() == (n0, s0)


def test_record_event_without_any_trace_is_cheap_and_silent():
    t0 = time.perf_counter()
    for i in range(2000):
        with RecordEvent("t26.off", step=i) as span:
            span.set(rows=1)
    per_span = (time.perf_counter() - t0) / 2000
    assert per_span < 50e-6        # about 1.5 us here; the bar is the order


def test_there_is_one_clock():
    assert flight._now_us is clock.now_us
    assert timeline._now_us is clock.now_us
    assert profiler.now_us is clock.now_us
    a = clock.now_us()
    b = clock.now_us()
    assert 0 <= b - a < 1e6


# -- names on the device side ----------------------------------------------

def test_serving_programs_carry_their_names_one_per_prefill_bucket(model):
    eng = _engine(model)
    eng.prefill(0, np.asarray(list(range(1, 14)), np.int32), budget=4)
    eng.prefill(1, np.asarray([7, 7, 7], np.int32), budget=4)
    eng.step()
    assert eng._decode._jitted.__wrapped__.__name__ == "serving_decode"
    assert sorted(eng._prefills) == [8]
    eng.release(0)
    eng.release(1)
    big = PagedLlamaDecodeEngine(model, max_slots=1, max_seq=64,
                                 block_size=8, prefill_chunk=16)
    big.prefill(0, np.asarray(list(range(1, 22)), np.int32), budget=4)
    names = {b: p._jitted.__wrapped__.__name__ for b, p in big._prefills.items()}
    assert names == {16: "serving_prefill_b16", 8: "serving_prefill_b8"}
    row = jax.numpy.asarray(big._kv.block_tables[0])
    i32 = jax.numpy.int32
    hlo = big._prefills[8]._jitted.lower(
        big.params, big.kvs, jax.numpy.zeros((1, 8), i32), row,
        i32(0), i32(3), i32(3)).as_text()
    assert "jit_serving_prefill_b8" in hlo


def test_spec_programs_carry_their_names(model):
    eng = _engine(model)
    eng.attach_draft(eng.make_draft(model, num_layers=1), spec_tokens=2)
    assert eng._spec_verify._jitted.__wrapped__.__name__ == "serving_spec_verify"
    assert eng._spec_propose._jitted.__wrapped__.__name__ == "serving_spec_draft"


def test_the_three_kernel_names_the_benchmark_matches_are_still_there():
    from paddle_tpu.ops.pallas import flash_attention, paged_attention
    assert callable(flash_attention._flash_fwd_pallas_blhd)
    assert callable(flash_attention._flash_bwd_pallas_blhd)
    assert callable(paged_attention._paged_attention_call)
    for fn, sub in ((flash_attention._flash_fwd_pallas_blhd, "_flash_fwd_pallas"),
                    (flash_attention._flash_bwd_pallas_blhd, "_flash_bwd_pallas"),
                    (paged_attention._paged_attention_call, "_paged_attention_call")):
        assert sub in fn.__name__


def test_paged_forward_carries_its_named_scopes(model):
    eng = _engine(model)
    s = eng.max_slots
    text = eng._decode._jitted.lower(
        eng.params, eng.kvs, jax.numpy.zeros((s, 1), np.int32),
        jax.numpy.zeros(s, np.int32), jax.numpy.asarray(eng._kv.block_tables),
        jax.numpy.zeros(s, bool)).as_text(debug_info=True)
    for scope in ("paged.kv_write", "paged.attn", "paged.mlp", "paged.head"):
        assert scope in text, scope


# -- the train step --------------------------------------------------------

@pytest.fixture(scope="module")
def traced_train(tmp_path_factory):
    from paddle_tpu.distributed.dist_train import DistTrainStep
    paddle.seed(3)
    net = LlamaForCausalLM(LlamaConfig.tiny(**CFG))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=list(net.parameters()))
    crit = LlamaPretrainingCriterion()
    step = DistTrainStep(net, lambda lg, lb: crit(lg, lb), opt)
    ids = np.arange(32, dtype=np.int32).reshape(2, 16) % 64
    trace_dir = tmp_path_factory.mktemp("trace26_train")
    _trace(trace_dir)
    try:
        losses = [float(step(ids, ids)) for _ in range(4)]
    finally:
        jax.profiler.stop_trace()
    (program,) = step._step._cache.values()
    return {"spans": _host_spans(str(trace_dir)), "losses": losses,
            "program": program, "stats": dict(step.stats)}


def test_train_step_spans_with_index_and_children(traced_train):
    steps = _named(traced_train, "train.step")
    assert [s[3]["step"] for s in steps] == [0, 1, 2, 3]
    assert all(s[3]["compiled"] == 1 for s in steps)
    for name in ("train.step.guard", "train.step.enqueue"):
        kids = _named(traced_train, name)
        assert len(kids) == 4
        for k, p in zip(kids, steps):
            assert p[0] <= k[0] and k[1] <= p[1]
    assert np.isfinite(traced_train["losses"]).all()


def test_the_compiling_call_is_a_named_span_under_the_step_that_paid(traced_train):
    compiles = _named(traced_train, "jit.compile")
    assert len(compiles) == traced_train["stats"]["compiles"] == 1
    c = compiles[0]
    assert c[3] == {"name": "dist_train_step", "kind": "train"}
    first = _named(traced_train, "train.step")[0]
    enq = _named(traced_train, "train.step.enqueue")[0]
    assert first[0] <= enq[0] <= c[0] and c[1] <= enq[1] <= first[1]


def test_the_captured_train_step_is_named_train_step(traced_train):
    assert traced_train["program"].__wrapped__.__name__ == "dist_train_step"


def test_capture_jit_compile_span_names_the_program(model, tmp_path):
    eng = _engine(model)
    _trace(tmp_path)
    try:
        eng.prefill(0, np.asarray([3, 1, 4], np.int32), budget=2)
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    compiles = [s[3] for s in _host_spans(str(tmp_path)) if s[2] == "jit.compile"]
    assert compiles == [{"name": "serving.prefill_b8", "kind": "capture_jit"},
                        {"name": "serving.decode", "kind": "capture_jit"}]


def test_train_step_scopes_reach_the_lowered_program():
    from paddle_tpu.jit.api import TrainStep
    paddle.seed(5)
    net = LlamaForCausalLM(LlamaConfig.tiny(**CFG))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=list(net.parameters()))
    crit = LlamaPretrainingCriterion()
    step = TrainStep(net, lambda lg, lb: crit(lg, lb), opt)
    ids = paddle.to_tensor(np.arange(32, dtype=np.int32).reshape(2, 16) % 64)
    cap = step._step
    arrays = cap._arrays([ids, ids])
    params, buffers, states = cap._gather(train=True)
    from paddle_tpu.optimizer.fused_step import _lr_device
    text = cap._build("train", 1).lower(
        params, buffers, states, _lr_device(opt),
        (jax.random.key(0), jax.numpy.uint32(0)), *arrays).as_text(debug_info=True)
    assert "jit_train_step" in text
    for scope in ("llama.embed", "llama.attn", "llama.mlp", "llama.head",
                  "loss", "optimizer"):
        assert scope in text, scope


def test_a_state_only_model_s_spans_count_the_state_they_move(tmp_path):
    """Brumby (every layer a state a slot, no block pool): each `serving.decode`
    span carries `state_slots`, the slots its launch steps, and
    `state_bytes_moved`, every layer's state of those slots read and
    written; each `serving.prefill` span carries `state_subchunks`, its
    chunk's rows in the chunk kernel's row tiles. No span counts a walk of
    blocks."""
    from paddle_tpu.models import BrumbyConfig, BrumbyForCausalLM
    paddle.seed(3)
    eng = PagedLlamaDecodeEngine(BrumbyForCausalLM(BrumbyConfig.tiny()),
                                 max_slots=2, max_seq=256, prefill_chunk=16)
    srv = GenerationServer(eng)
    _serve(srv, [list(range(40, 50))])          # compile outside the trace
    _trace(tmp_path)
    try:
        _serve(srv, [list(range(1, 40)), [5, 9, 11]])
    finally:
        jax.profiler.stop_trace()
        srv.shutdown()
    spans = _host_spans(str(tmp_path))
    decodes = [st for _, _, name, st, _ in spans if name == "serving.decode"]
    prefills = [st for _, _, name, st, _ in spans if name == "serving.prefill"]
    assert len(decodes) >= NEW and len(prefills) >= 3
    for st in decodes:
        assert st["state_slots"] == st["rows"]
        assert st["state_bytes_moved"] \
            == 2 * st["rows"] * eng.state_slot_bytes
        assert "walk_tokens" not in st
    assert {st["state_subchunks"] for st in prefills} == {1}
    assert sorted(st["tokens"] for st in prefills) == [3, 7, 16, 16]
