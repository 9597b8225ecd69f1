"""Hot-start fleet (ISSUE 14): persistent executable cache, warm-bundle
boot pre-warm, and zero-downtime weight hot-swap.

Three planes pinned here:

- **Executable cache** (``jit.warmup.cache_dir()``): compiled XLA
  artifacts persist on disk; a poisoned entry degrades to a counted
  miss + recompile, never a crash. The acceptance scenario runs TWO
  real processes against one cache dir + bundle: the second reaches
  its first captured train step and its first decode token with ZERO
  fresh XLA compiles (``executable_cache.misses_total == 0``,
  ``writes_total == 0``, counters pinned).
- **Warm bundle** (``jit.warmup``): record -> export -> load -> prewarm
  round-trips; a truncated/corrupt/over-versioned bundle falls back to
  cold compile with a counted ``warmup.failures_total{reason}``;
  pre-warm pre-populates the CapturedStep cache so the FIRST batch
  runs captured.
- **Weight hot-swap** (``GenerationServer.swap_weights``): applied
  between decode steps on the loop thread — a same-weights swap
  mid-stream leaves the greedy stream BIT-equal across the boundary
  (nothing dropped or corrupted), twin engines swapped to the same new
  weights stay in lockstep (the logits switch is a pure function of
  the new weights + shared pre-swap KV), allocator invariants hold,
  a weight-sharing draft re-aliases in the same swap, and a
  shape-mismatched checkpoint is rejected with the old weights intact.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.hapi import Model
from paddle_tpu.jit import warmup
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine
from paddle_tpu.utils import fault_injection as fi

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, use_flash_attention=False)
GEO = dict(max_slots=2, max_seq=128, block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module", autouse=True)
def module_cache(tmp_path_factory):
    """One shared persistent executable cache for the whole module: the
    tiny llama engines these tests build all compile the SAME programs,
    so with the cache on, engine #2..N deserialize from disk instead of
    recompiling — the feature under test keeping its own tests fast.
    Per-test counter assertions still hold: they measure deltas."""
    d = str(tmp_path_factory.mktemp("hot_start_module_cache"))
    paddle.set_flags({"FLAGS_executable_cache_dir": d})
    warmup.ensure_executable_cache()
    try:
        yield d
    finally:
        paddle.set_flags({"FLAGS_executable_cache_dir": ""})
        warmup.ensure_executable_cache()


@pytest.fixture
def cache_dir(tmp_path, module_cache):
    """Enable the executable cache in a throwaway dir for one test
    (isolated counters/artifacts, e.g. for poisoning) and restore the
    module-wide cache afterwards (the next compile seam's ensure()
    call re-reads the flag, so flipping it back suffices)."""
    d = str(tmp_path / "xla_cache")
    paddle.set_flags({"FLAGS_executable_cache_dir": d})
    warmup.ensure_executable_cache()
    try:
        yield d
    finally:
        paddle.set_flags({"FLAGS_executable_cache_dir": module_cache})
        warmup.ensure_executable_cache()


def _model_a():
    paddle.seed(7)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


def _model_b():
    paddle.seed(13)
    return LlamaForCausalLM(LlamaConfig.tiny(**CFG))


def _hapi_model(seed=0):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Linear(16, 3))
    m = Model(net)
    m.prepare(optimizer=paddle.optimizer.Adam(
        learning_rate=0.01, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    return m


def _toy_batch():
    rng = np.random.default_rng(0)
    return (rng.normal(size=(8, 4)).astype(np.float32),
            rng.integers(0, 3, 8).astype(np.int64))


def _pool_invariants(kv):
    st = kv.stats()
    owned = sum(len(b) for b in kv._owned.values())
    shared = sum(len(b) for b in kv._shared.values())
    # three-way partition: free / privately-owned / held by the
    # prefix radix tree (aliased blocks live in the tree, counted
    # once however many slots map them)
    assert st["blocks_free"] + owned + st["blocks_cached"] \
        == kv.num_blocks
    assert st["blocks_reserved"] == sum(kv._reserved.values())
    mapped = int((kv.block_tables >= 0).sum())
    assert mapped == owned + shared
    for row in kv.block_tables:
        row = row[row >= 0]
        assert len(set(row.tolist())) == len(row)
    kv.check_invariants()


# ---------------------------------------------------------------------------
# persistent executable cache
# ---------------------------------------------------------------------------

class TestExecutableCache:
    def test_directory_rules(self, module_cache, tmp_path, monkeypatch):
        """Where the cache lives, in order: JAX_COMPILATION_CACHE_DIR
        from the environment (JAX reads it itself; the program then
        never sets the directory, whatever the flag says), else
        FLAGS_executable_cache_dir, else ONE fixed .jax_cache beside
        the package. Counters and thresholds are installed each way."""
        import jax
        updates = []
        real_update = jax.config.update

        def spy(name, value):
            updates.append(name)
            real_update(name, value)

        env_dir, flag_dir = str(tmp_path / "env"), str(tmp_path / "flag")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        try:
            # 3. neither: fixed, derived from the package's location
            paddle.set_flags({"FLAGS_executable_cache_dir": ""})
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert warmup.cache_dir() == os.path.join(repo, ".jax_cache")
            assert warmup.cache_dir() == warmup.cache_dir()
            # 2. the flag
            paddle.set_flags({"FLAGS_executable_cache_dir": flag_dir})
            assert warmup.ensure_executable_cache() == flag_dir
            assert jax.config.jax_compilation_cache_dir == flag_dir
            # 1. the environment wins and the program sets nothing
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            monkeypatch.setattr(jax.config, "update", spy)
            assert warmup.ensure_executable_cache() == env_dir
            assert "jax_compilation_cache_dir" not in updates
            assert "jax_persistent_cache_min_compile_time_secs" in updates
            assert not os.path.exists(env_dir)   # JAX's to create
        finally:
            monkeypatch.undo()
            paddle.set_flags(
                {"FLAGS_executable_cache_dir": module_cache})
            warmup.ensure_executable_cache()

    def test_roundtrip_and_poisoned_entry(self, cache_dir):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.jit.sot import capture_jit

        fn = capture_jit(lambda x: x * 2 + 1, name="hot_start_probe")
        x = jnp.asarray(np.arange(6, dtype=np.float32))
        before = warmup.cache_stats()
        np.testing.assert_allclose(np.asarray(fn(x)),
                                   np.arange(6) * 2 + 1)
        mid = warmup.cache_stats()
        assert mid["writes"] > before["writes"]
        assert mid["misses"] > before["misses"]
        # a fresh process re-traces but reads the artifact from disk:
        # clear_caches simulates the restart inside this process
        jax.clear_caches()
        np.testing.assert_allclose(np.asarray(fn(x)),
                                   np.arange(6) * 2 + 1)
        after = warmup.cache_stats()
        assert after["hits"] > mid["hits"]
        assert after["writes"] == mid["writes"]
        # poison EVERY cache artifact: the next compile must degrade to
        # a counted miss + fresh compile, never crash
        poisoned = 0
        for root, _dirs, files in os.walk(cache_dir):
            for name in files:
                with open(os.path.join(root, name), "wb") as f:
                    f.write(b"\x00poison\xff" * 8)
                poisoned += 1
        assert poisoned > 0
        jax.clear_caches()
        import warnings as _warnings
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            np.testing.assert_allclose(np.asarray(fn(x)),
                                       np.arange(6) * 2 + 1)
        final = warmup.cache_stats()
        assert final["misses"] > after["misses"]


# ---------------------------------------------------------------------------
# warm bundle: record / export / load / prewarm
# ---------------------------------------------------------------------------

class TestWarmBundle:
    def test_record_export_prewarm_captured_step(self, tmp_path):
        # other suite tests' captured steps (different models) are in
        # the cumulative recording; this test pins THIS run's round
        # trip, so start from a clean manifest — replaying a foreign
        # geometry into m2 is a counted failure by design
        warmup.clear_recorded()
        X, y = _toy_batch()
        m = _hapi_model()
        losses = [float(m.train_batch([X], [y])[0])
                  for _ in range(3)]
        ref = losses[0]  # same-point comparison for m2's FIRST step
        entries = [e for e in warmup.recorded()
                   if e["kind"] == "captured_step"]
        assert entries and entries[-1]["build"] == "train"
        assert entries[-1]["sig"] is not None
        path = warmup.export_bundle(str(tmp_path / "wb.json"))
        bundle = warmup.load_bundle(path)
        assert bundle["entries"]

        m2 = _hapi_model()
        out = warmup.prewarm(bundle, captured=m2._captured or
                             m2._capture_engine())
        assert out["programs"] >= 1 and out["failures"] == 0
        # the FIRST batch runs captured: no first-sighting eager step,
        # no fresh program build
        loss2 = m2.train_batch([X], [y])
        eng = m2._captured
        assert eng.stats["eager_steps"] == 0
        assert eng.stats["compiles"] == 0
        assert eng.stats["captured_steps"] == 1
        assert eng.stats["cache_hits"] == 1
        np.testing.assert_allclose(float(loss2[0]), ref, rtol=1e-5)

    def test_prepare_warm_bundle_kwarg(self, tmp_path):
        X, y = _toy_batch()
        m = _hapi_model()
        for _ in range(3):
            m.train_batch([X], [y])
        path = warmup.export_bundle(str(tmp_path / "wb.json"))
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 16), nn.Tanh(),
                            nn.Linear(16, 3))
        m2 = Model(net)
        m2.prepare(optimizer=paddle.optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss(), warm_bundle=path)
        m2.train_batch([X], [y])
        assert m2._captured.stats["eager_steps"] == 0

    def test_prewarm_serving_programs(self):
        mA = _model_a()
        eng = PagedLlamaDecodeEngine(mA, **GEO)
        ref = eng.generate([1, 2, 3, 4], max_new_tokens=6)
        path = warmup.export_bundle()
        eng2 = PagedLlamaDecodeEngine(mA, **GEO)
        out = warmup.prewarm(path, engine=eng2)
        # decode + at least one prefill bucket replayed
        assert out["programs"] >= 2 and out["failures"] == 0
        assert eng2.generate([1, 2, 3, 4], max_new_tokens=6) == ref

    def test_spec_entries_skipped_without_draft(self):
        mA = _model_a()
        eng = PagedLlamaDecodeEngine(mA, **GEO)
        eng.attach_draft(eng.make_draft(mA, num_layers=1),
                         spec_tokens=3)
        srv = GenerationServer(eng)  # the loop runs spec_step
        srv.generate([1, 2, 3, 4], max_new_tokens=6)
        srv.shutdown()
        bundle = warmup.load_bundle(warmup.export_bundle())
        kinds = {e["meta"]["program"] for e in bundle["entries"]
                 if e["kind"] == "serving"}
        assert {"spec_draft", "spec_verify"} <= kinds
        plain = PagedLlamaDecodeEngine(mA, **GEO)  # no draft attached
        out = warmup.prewarm(bundle, engine=plain)
        assert out["failures"] == 0 and out["skipped"] >= 2


class TestBundleFaults:
    @staticmethod
    def _reason_count(reason):
        from paddle_tpu.jit.warmup import _M_failures
        return _M_failures.value(reason=reason)

    def test_truncated_bundle_falls_back(self, tmp_path):
        path = warmup.export_bundle(str(tmp_path / "wb.json"))
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[:max(4, len(blob) // 3)])
        before = self._reason_count("corrupt")
        assert warmup.load_bundle(path) is None
        assert self._reason_count("corrupt") == before + 1
        # boot continues cold: prewarm of the damaged bundle is a no-op
        out = warmup.prewarm(path, captured=None, engine=None)
        assert out == {"programs": 0, "failures": 0, "skipped": 0}

    def test_missing_bundle_counted(self, tmp_path):
        before = self._reason_count("missing")
        assert warmup.load_bundle(str(tmp_path / "nope.json")) is None
        assert self._reason_count("missing") == before + 1

    def test_version_gate(self, tmp_path):
        path = str(tmp_path / "future.json")
        with open(path, "w") as f:
            json.dump({"__paddle_tpu_warm_bundle__": 999,
                       "entries": []}, f)
        before = self._reason_count("version")
        assert warmup.load_bundle(path) is None
        assert self._reason_count("version") == before + 1

    def test_truncated_write_leaves_no_bundle(self, tmp_path):
        path = str(tmp_path / "wb.json")
        with fi.injected("warmup.write", truncate_at=16):
            with pytest.raises(Exception):
                warmup.export_bundle(path)
        assert not os.path.exists(path)
        assert not [n for n in os.listdir(tmp_path)
                    if n.startswith("wb.json.tmp")]

    def test_unreplayable_entry_counted_not_fatal(self):
        mA = _model_a()
        eng = PagedLlamaDecodeEngine(mA, **GEO)
        bundle = {"__paddle_tpu_warm_bundle__": 1, "entries": [
            {"kind": "serving", "name": "x",
             "meta": {"program": "prefill", "bucket": -3}},
            {"kind": "captured_step", "name": "y", "build": "bogus"},
            "not-a-dict"]}
        before = self._reason_count("program")
        out = warmup.prewarm(bundle, captured=object(), engine=eng)
        assert out["failures"] >= 1
        assert self._reason_count("program") >= before + 1
        # the engine still serves (cold) after the failed pre-warm
        assert len(eng.generate([1, 2], max_new_tokens=3)) == 3

    def test_stale_geometry_bundle_degrades_counted(self):
        """Freshness check (ISSUE 15): a bundle recorded by a replica
        with a DIFFERENT serving geometry must not be silently
        replayed — its entries would compile fresh programs at boot
        while the counters claim warmth. Every serving entry fails as
        reason=stale and the engine still boots (cold)."""
        warmup.clear_recorded()
        mA = _model_a()
        eng = PagedLlamaDecodeEngine(mA, **GEO)
        eng.generate([1, 2, 3], max_new_tokens=4)
        bundle = warmup.load_bundle(warmup.export_bundle())
        n_serving = sum(1 for e in bundle["entries"]
                        if e["kind"] == "serving")
        assert n_serving >= 2  # decode + >= 1 prefill bucket
        # entries carry the recording geometry
        metas = [e["meta"] for e in bundle["entries"]
                 if e["kind"] == "serving"]
        assert all(m["layout"] == "paged"
                   and m["block_size"] == GEO["block_size"]
                   for m in metas)
        other = PagedLlamaDecodeEngine(mA, max_slots=2, max_seq=128,
                                      block_size=16, prefill_chunk=8)
        before = self._reason_count("stale")
        out = warmup.prewarm(bundle, engine=other)
        assert out["programs"] == 0
        assert out["failures"] == n_serving
        assert self._reason_count("stale") == before + n_serving
        # the MATCHING geometry still replays everything
        twin = PagedLlamaDecodeEngine(mA, **GEO)
        out2 = warmup.prewarm(bundle, engine=twin)
        assert out2["programs"] >= 2 and out2["failures"] == 0
        # precision: a replica differing ONLY in prefill chunk keeps
        # all its warmth — no program's shape depends on the chunk
        # (recorded buckets still fit under the larger live chunk)
        chunky = PagedLlamaDecodeEngine(mA, max_slots=2, max_seq=128,
                                        block_size=8, prefill_chunk=16)
        out3 = warmup.prewarm(bundle, engine=chunky)
        assert out3["programs"] >= 2 and out3["failures"] == 0


# ---------------------------------------------------------------------------
# cache-dir GC by last-hit age
# ---------------------------------------------------------------------------

class TestCacheDirGC:
    def test_evicts_by_last_hit_age_only(self, tmp_path):
        """Old cache artifacts age out (counted); fresh entries,
        warm-bundle manifests and subdirectories are never touched."""
        from paddle_tpu.jit.warmup import _M_evicted
        d = tmp_path / "xla_cache"
        d.mkdir()
        old = d / "jit__decode-abc123"
        old.write_bytes(b"stale artifact")
        stamp = time.time() - 3 * 86400
        os.utime(old, (stamp, stamp))
        fresh = d / "jit__prefill-def456"
        fresh.write_bytes(b"fresh artifact")
        manifest = d / "warm_bundle.json"
        manifest.write_text("{}")
        os.utime(manifest, (stamp, stamp))  # old but a manifest
        sub = d / "subdir"
        sub.mkdir()
        before = _M_evicted.value()
        assert warmup.gc_cache_dir(max_age_days=1,
                                   directory=str(d)) == 1
        assert not old.exists()
        assert fresh.exists() and manifest.exists() and sub.exists()
        assert _M_evicted.value() == before + 1
        # disabled (the flag default) is a no-op
        assert warmup.gc_cache_dir(max_age_days=0,
                                   directory=str(d)) == 0
        assert warmup.gc_cache_dir(directory=str(d)) == 0
        assert fresh.exists()


# ---------------------------------------------------------------------------
# zero-downtime weight swap
# ---------------------------------------------------------------------------

class TestWeightSwapEngine:
    def test_twin_engines_stay_lockstep_through_swap(self):
        """Two identical engines decode in lockstep; both swap to the
        same NEW weights mid-stream and must STAY in lockstep (the
        post-swap step is a pure function of the new weights + the
        shared pre-swap KV) while diverging from an unswapped third —
        the logits switched at the step boundary."""
        mA, mB = _model_a(), _model_b()
        sd_b = mB.state_dict()
        engines = [PagedLlamaDecodeEngine(mA, **GEO) for _ in range(3)]
        prompt = [1, 2, 3, 4, 5]
        firsts = {eng.prefill(0, prompt, budget=40) for eng in engines}
        assert len(firsts) == 1
        pre = [[int(eng.step()[0]) for _ in range(3)]
               for eng in engines]
        assert pre[0] == pre[1] == pre[2]
        engines[0].swap_weights(sd_b)
        engines[1].swap_weights(sd_b)
        post = [[int(eng.step()[0]) for _ in range(8)]
                for eng in engines]
        assert post[0] == post[1]          # swap is deterministic
        assert post[0] != post[2]          # and actually took effect
        for eng in engines:
            _pool_invariants(eng._kv)
            eng.release(0)

    def test_engine_swap_rejects_shape_mismatch(self):
        mA = _model_a()
        eng = PagedLlamaDecodeEngine(mA, **GEO)
        ref = eng.generate([3, 2, 1], max_new_tokens=5)
        old_params = eng.params
        paddle.seed(5)
        wrong = LlamaForCausalLM(LlamaConfig.tiny(
            **dict(CFG, hidden_size=16, intermediate_size=32)))
        with pytest.raises(ValueError):
            eng.swap_weights(wrong.state_dict())
        assert eng.params is old_params
        missing = dict(mA.state_dict())
        missing.pop("llama.norm.weight")
        with pytest.raises(ValueError):
            eng.swap_weights(missing)
        assert eng.params is old_params
        assert eng.generate([3, 2, 1], max_new_tokens=5) == ref


class TestWeightSwapServer:
    def _serve(self, model, **kw):
        geo = dict(GEO, **kw)
        return GenerationServer(PagedLlamaDecodeEngine(model, **geo))

    def test_same_weights_swap_is_bit_transparent(self):
        """A mid-decode swap to IDENTICAL weights must leave the
        in-flight greedy stream bit-equal to a never-swapped run: no
        token dropped, duplicated or corrupted across the boundary."""
        mA = _model_a()
        ref_srv = self._serve(mA)
        prompt = list(range(1, 9))
        ref = ref_srv.generate(prompt, max_new_tokens=40)
        ref_srv.shutdown()

        srv = self._serve(mA)
        req = srv.submit(prompt, max_new_tokens=40)
        deadline = time.monotonic() + 30
        while len(req["out"]) < 4 and time.monotonic() < deadline:
            time.sleep(0.001)
        res = srv.swap_weights(mA.state_dict())
        assert res["seconds"] >= 0
        assert req["done"].wait(60)
        assert list(req["out"]) == ref
        assert srv.stats()["weight_swaps"] == 1
        _pool_invariants(srv.engine._kv)
        srv.shutdown()

    def test_mid_stream_swap_switches_weights(self):
        """A mid-decode swap to NEW weights: the request keeps
        streaming to its full budget (nothing dropped), the engine's
        tree is the new one, and a post-swap request matches a fresh
        engine booted on the new weights."""
        mA, mB = _model_a(), _model_b()
        sd_b = mB.state_dict()
        srv = self._serve(mA)
        prompt = [2, 4, 6, 8]
        first_a = srv.generate(prompt, max_new_tokens=2)[0]
        req = srv.submit(prompt, max_new_tokens=60)
        deadline = time.monotonic() + 30
        while len(req["out"]) < 4 and time.monotonic() < deadline:
            time.sleep(0.001)
        srv.swap_weights(sd_b)
        assert req["done"].wait(60)
        assert len(req["out"]) == 60
        assert req["out"][0] == first_a  # pre-swap prefix from A
        _pool_invariants(srv.engine._kv)
        # a fresh request now runs fully on B
        post = srv.generate(prompt, max_new_tokens=8)
        engB = PagedLlamaDecodeEngine(mB, **GEO)
        assert post == engB.generate(prompt, max_new_tokens=8)
        srv.shutdown()

    def test_server_swap_rejection_keeps_serving(self):
        from paddle_tpu.serving import _M_swap_rejected
        mA = _model_a()
        srv = self._serve(mA)
        ref = srv.generate([1, 2, 3], max_new_tokens=6)
        bad = dict(mA.state_dict())
        bad.pop("llama.norm.weight")
        before = _M_swap_rejected.value()
        with pytest.raises(ValueError):
            srv.swap_weights(bad)
        assert _M_swap_rejected.value() == before + 1
        assert srv.stats()["weight_swaps"] == 0
        assert srv.generate([1, 2, 3], max_new_tokens=6) == ref
        srv.shutdown()

    def test_draft_rolls_with_target(self):
        mA, mB = _model_a(), _model_b()
        eng = PagedLlamaDecodeEngine(mA, **GEO)
        eng.attach_draft(eng.make_draft(mA, num_layers=1),
                         spec_tokens=3)
        srv = GenerationServer(eng)
        srv.generate([1, 2, 3, 4], max_new_tokens=6)
        srv.swap_weights(mB.state_dict())
        draft = eng._draft
        assert draft.params["emb"] is eng.params["emb"]
        for i in range(draft.n_layers):
            for nm, leaf in draft.params["layers"][i].items():
                assert leaf is eng.params["layers"][i][nm]
        # post-swap speculative stream == plain engine on B (the spec
        # bit-equality contract survives the swap)
        out = srv.generate([9, 8, 7], max_new_tokens=8)
        plain = PagedLlamaDecodeEngine(mB, **GEO)
        assert out == plain.generate([9, 8, 7], max_new_tokens=8)
        _pool_invariants(eng._kv)
        _pool_invariants(draft._kv)
        srv.shutdown()

    def test_swap_from_checkpoint_manager_and_path(self, tmp_path):
        from paddle_tpu.framework.checkpoint import CheckpointManager
        mA, mB = _model_a(), _model_b()
        mgr = CheckpointManager(str(tmp_path / "ck"), keep_n=2)
        mgr.save({"model": mA.state_dict(), "step": 0}, step=0)
        path_b = mgr.save({"model": mB.state_dict(), "step": 1}, step=1)
        srv = self._serve(mA)
        srv.swap_weights(mgr)  # newest good checkpoint = B
        engB = PagedLlamaDecodeEngine(mB, **GEO)
        refB = engB.generate([5, 6, 7], max_new_tokens=6)
        assert srv.generate([5, 6, 7], max_new_tokens=6) == refB
        srv.swap_weights(path_b)  # explicit path form
        assert srv.generate([5, 6, 7], max_new_tokens=6) == refB
        assert srv.stats()["weight_swaps"] == 2
        srv.shutdown()

    def test_swap_after_shutdown_rejected(self):
        mA = _model_a()
        srv = self._serve(mA)
        srv.shutdown()
        with pytest.raises(RuntimeError):
            srv.swap_weights(mA.state_dict())


# ---------------------------------------------------------------------------
# the restart acceptance: second process = zero fresh XLA compiles
# ---------------------------------------------------------------------------

_WORKER = r'''
import json, os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["FLAGS_executable_cache_dir"] = os.environ["HS_CACHE_DIR"]
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.hapi import Model
from paddle_tpu.jit import warmup
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import PagedLlamaDecodeEngine

bundle = os.environ.get("HS_BUNDLE") or None
paddle.seed(0)
net = nn.Sequential(nn.Linear(4, 16), nn.Tanh(), nn.Linear(16, 3))
m = Model(net)
m.prepare(optimizer=paddle.optimizer.Adam(
    learning_rate=0.01, parameters=net.parameters()),
    loss=nn.CrossEntropyLoss(), warm_bundle=bundle)
rng = np.random.default_rng(0)
X = rng.normal(size=(8, 4)).astype(np.float32)
y = rng.integers(0, 3, 8).astype(np.int64)
loss = None
for _ in range(3):
    loss = m.train_batch([X], [y])
paddle.seed(1)
lm = LlamaForCausalLM(LlamaConfig.tiny(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    use_flash_attention=False))
eng = PagedLlamaDecodeEngine(lm, max_slots=1, max_seq=64, block_size=8,
                             prefill_chunk=8)
if bundle:
    warmup.prewarm(bundle, engine=eng)
toks = eng.generate([1, 2, 3], max_new_tokens=4)
export = os.environ.get("HS_EXPORT")
if export:
    warmup.export_bundle(export)
    # seal the bundle: persist the AOT-lowered flavors of every
    # recorded program so a pre-warmed boot is 100% disk hits
    warmup.prewarm(export, captured=m._captured, engine=eng)
print(json.dumps({"cache": warmup.cache_stats(),
                  "sot": {k: v for k, v in m._captured.stats.items()
                          if k != "fallbacks"},
                  "toks": [int(t) for t in toks],
                  "loss": float(loss[0])}))
'''


def _run_worker(cache_dir, bundle=None, export=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HS_CACHE_DIR=str(cache_dir))
    env.pop("FLAGS_executable_cache_dir", None)
    env.pop("FLAGS_warmup_bundle", None)
    if bundle:
        env["HS_BUNDLE"] = str(bundle)
    if export:
        env["HS_EXPORT"] = str(export)
    r = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_second_process_boots_with_zero_fresh_compiles(tmp_path):
    """THE acceptance scenario: process 1 boots cold against an empty
    cache dir, trains a captured step and decodes tokens, exports the
    warm bundle. Process 2 — same cache dir, pre-warmed from the
    bundle — reaches its first captured train step AND its first
    decode token with ZERO fresh XLA compiles: every compile is a
    persistent-cache disk hit (misses == 0, writes == 0, counters
    pinned), the first train_batch runs captured (no first-sighting
    eager step), and the streams/losses are bit-identical."""
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    bundle = cache / "warm_bundle.json"
    cold = _run_worker(cache, export=bundle)
    assert cold["cache"]["writes"] > 0
    assert cold["cache"]["misses"] > 0
    assert bundle.exists()

    warm = _run_worker(cache, bundle=bundle)
    assert warm["cache"]["misses"] == 0, warm
    assert warm["cache"]["writes"] == 0, warm
    assert warm["cache"]["hits"] > 0, warm
    # first batch ran captured: pre-warm pre-populated the program
    assert warm["sot"]["eager_steps"] == 0, warm
    assert warm["sot"]["compiles"] == 0, warm
    assert warm["sot"]["captured_steps"] == 3, warm
    # and the warm boot computes the same numbers
    assert warm["toks"] == cold["toks"]
    assert warm["loss"] == pytest.approx(cold["loss"], rel=1e-6)
