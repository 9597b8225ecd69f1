"""Inference engine: saved model -> compiled serving predictor.

ref: paddle/fluid/inference/api/analysis_predictor.h (AnalysisPredictor:
load program+params, run analysis/fusion passes, zero-copy IO) and
python/paddle/inference (Config + create_predictor). The TPU analog: the
"analysis passes + fusion" role belongs to XLA — a Predictor functionalizes
the model, jit-compiles forward per input signature (shape/dtype-keyed
cache), and serves batches. Saved artifacts are paddle.jit.save outputs:
state_dict + a model-factory reference, so a server process can
reconstruct without the training script.
"""
from __future__ import annotations

import importlib
import os
import inspect
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .core.tensor import Tensor
from .framework.io import load as _load, save as _save
from .jit.api import functionalize

__all__ = ["Config", "Predictor", "create_predictor", "save_inference_model",
           "load_inference_model", "serve"]


def _forced_eval_fwd(model, apply):
    """Forward that serves in eval semantics without disturbing the
    caller's per-sublayer modes."""
    def fwd(params, buffers, *args):
        layers = model.sublayers(include_self=True)
        snapshot = [(l, l.training) for l in layers]
        try:
            for l in layers:
                l.training = False
            out, _ = apply(params, buffers, *args)
        finally:
            for l, t in snapshot:
                l.training = t
        return out
    return fwd


def _export_aot(model, input_spec):
    """AOT-serialize the compiled eval forward via jax.export — the
    StableHLO travels inside the artifact, so a serving process can run
    it WITHOUT the model's Python class being importable
    (ref: AnalysisPredictor loads a self-contained program+params;
    the reference never needs the training script either)."""
    apply, params, buffers = functionalize(model)
    jitted = jax.jit(_forced_eval_fwd(model, apply))
    arg_avals = []
    for s in input_spec:
        if any(d is None or int(d) <= 0 for d in s.shape):
            raise ValueError(
                f"AOT export needs fully-static input shapes, got "
                f"{list(s.shape)} (use bucketing for varlen serving)")
        shape = tuple(int(d) for d in s.shape)
        arg_avals.append(jax.ShapeDtypeStruct(shape, jnp.dtype(s.dtype)))
    p_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for k, v in params.items()}
    b_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for k, v in buffers.items()}
    exported = jax.export.export(jitted)(p_avals, b_avals, *arg_avals)
    return {
        "blob": exported.serialize(),
        "param_keys": sorted(params),
        "buffer_keys": sorted(buffers),
    }


def save_inference_model(path: str, model, input_spec=None, aot=False):
    """ref: paddle.static.save_inference_model / jit.save — persist params
    plus the importable factory so inference can rebuild the module.
    input_spec (shapes/dtypes) is stored for consumers that pre-compile.

    Reconstructability is validated AT SAVE TIME: a model whose __init__
    needs arguments must expose them as `.config` (the LM zoo convention),
    otherwise load would fail later in the serving process.
    """
    cls = type(model)
    cfg = getattr(model, "config", None)
    if cfg is None:
        sig = inspect.signature(cls.__init__)
        P_ = inspect.Parameter
        required = [
            n for n, p in list(sig.parameters.items())[1:]
            if (p.kind in (P_.POSITIONAL_OR_KEYWORD, P_.POSITIONAL_ONLY,
                           P_.KEYWORD_ONLY)
                and p.default is P_.empty)
            or p.kind is P_.VAR_POSITIONAL  # e.g. Sequential(*layers)
        ]
        if required:
            raise ValueError(
                f"cannot save {cls.__qualname__} for inference: __init__ "
                f"takes {required} but the model has no .config "
                "attribute to rebuild from. Store constructor arguments "
                "on `self.config`, or save weights only via paddle.save")
    payload = {
        "state_dict": model.state_dict(),
        "module": cls.__module__,
        "class_name": cls.__qualname__,
        "init_config": cfg,
        "input_spec": [
            {"shape": list(s.shape), "dtype": str(s.dtype)}
            for s in (input_spec or [])
        ],
    }
    if aot:
        if not input_spec:
            raise ValueError(
                "save_inference_model(aot=True) needs input_spec to fix "
                "the exported program's signature")
        payload["aot"] = _export_aot(model, input_spec)
    _save(payload, path + ".pdmodel")


def load_inference_model(path: str, _payload=None):
    """Rebuild the Layer from a save_inference_model artifact. Raises if
    the reconstructed module's parameters don't match the checkpoint —
    serving silently-random weights is the worst failure mode."""
    payload = _payload if _payload is not None else _load(
        path + ".pdmodel", return_numpy=False)
    mod = importlib.import_module(payload["module"])
    cls = mod
    for part in payload["class_name"].split("."):
        cls = getattr(cls, part)
    cfg = payload["init_config"]
    model = cls(cfg) if cfg is not None else cls()
    # install weights preserving the CHECKPOINT dtype (a bf16-saved model
    # must serve in bf16)
    missing, unexpected = model.set_state_dict(payload["state_dict"],
                                               cast_dtype=False)
    if missing or unexpected:
        raise ValueError(
            f"saved model does not match reconstructed "
            f"{payload['class_name']}: missing={missing[:5]}, "
            f"unexpected={unexpected[:5]}")
    model.eval()
    return model


class Config:
    """ref: paddle.inference.Config — carries the model path + runtime
    options (the CUDA/TensorRT knobs become XLA-level choices here)."""

    def __init__(self, model_path: Optional[str] = None):
        self.model_path = model_path
        self._bf16 = False

    def enable_bf16(self):
        self._bf16 = True

    # GPU-era knobs kept as accepted no-ops for API compatibility (XLA
    # already does the fusion/memory planning these toggled)
    def enable_memory_optim(self, *a, **k):
        return None

    def enable_use_gpu(self, *a, **k):
        return None

    def switch_ir_optim(self, *a, **k):
        return None


class Predictor:
    """Compiled serving wrapper (ref: AnalysisPredictor::Run contract:
    named inputs in, named outputs out, internal exec state reused)."""

    def __init__(self, model_or_config):
        self._cache_key_base = None
        self._aot = None
        if isinstance(model_or_config, Config):
            cfg = model_or_config
            if cfg.model_path is None:
                raise ValueError(
                    "Config has no model_path; pass Config(path) pointing "
                    "at a save_inference_model artifact")
            payload = _load(cfg.model_path + ".pdmodel",
                            return_numpy=False)
            if payload.get("aot"):
                if cfg._bf16:
                    raise ValueError(
                        "enable_bf16() cannot re-cast an AOT artifact "
                        "(its compiled signature is fixed at export); "
                        "save with a bf16 model instead")
                # AOT warm start: the serialized StableHLO serves without
                # the model class being importable in this process
                self._init_aot(payload)
                return
            model = load_inference_model(cfg.model_path, _payload=payload)
            if cfg._bf16:
                model.bfloat16()
            # artifact-backed predictors share compiled executables
            # process-wide through the native ExecCache (KernelFactory
            # analog): a second Predictor on the same path skips compile.
            # mtime+size in the key invalidate on artifact overwrite (the
            # replaced cache entry drops the old model's closure).
            art = cfg.model_path + ".pdmodel"
            st = os.stat(art)
            self._cache_key_base = \
                f"predictor|{os.path.abspath(cfg.model_path)}" \
                f"|{st.st_mtime_ns}|{st.st_size}|bf16={cfg._bf16}"
        else:
            model = model_or_config
        self.model = model
        apply, params, buffers = functionalize(model)
        self._apply = apply
        self._params = params
        self._buffers = buffers

        fwd = _forced_eval_fwd(model, apply)

        from ._native import lib as _nlib
        use_cache = self._cache_key_base is not None and _nlib is not None
        cached = (_nlib.exec_cache_get(self._cache_key_base)
                  if use_cache else None)
        # (re)compile or reuse the jitted callable — its XLA compile cache
        # comes with it; params/buffers bind per run() call
        self._jitted = cached if cached is not None else jax.jit(fwd)
        if use_cache and cached is None:
            # evict entries for older versions of this artifact first —
            # their keys (old mtime/size) would otherwise pin the old
            # model's weights until cap eviction
            prefix = self._cache_key_base.rsplit("|", 3)[0] + "|"
            _nlib.exec_cache_evict_prefix(prefix)
            _nlib.exec_cache_put(self._cache_key_base, self._jitted)

    def _init_aot(self, payload):
        exported = jax.export.deserialize(payload["aot"]["blob"])
        sd = payload["state_dict"]

        def arr(v):
            return v._data if isinstance(v, Tensor) else jnp.asarray(v)

        self._params = {k: arr(sd[k]) for k in payload["aot"]["param_keys"]}
        self._buffers = {k: arr(sd[k])
                         for k in payload["aot"]["buffer_keys"]}
        self._aot = exported
        self.model = None
        self._input_spec = payload.get("input_spec", [])

    def run(self, *inputs):
        """numpy/Tensor/jax-array inputs -> list of numpy outputs."""
        raw = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
               for i in inputs]
        if self._aot is not None:
            out = self._aot.call(self._params, self._buffers, *raw)
        else:
            out = self._jitted(self._params, self._buffers, *raw)
        if isinstance(out, (tuple, list)):
            return [np.asarray(o) for o in out]
        return [np.asarray(out)]

    # reference-style named-handle API: names come from the model's
    # forward signature
    def get_input_names(self) -> Sequence[str]:
        if self._aot is not None:
            return [f"input_{i}" for i in range(len(self._input_spec))]
        sig = inspect.signature(self.model.forward)
        return [n for n, p in sig.parameters.items()
                if p.default is inspect.Parameter.empty
                and p.kind in (p.POSITIONAL_OR_KEYWORD, p.POSITIONAL_ONLY)]

    def predict(self, *inputs):
        return self.run(*inputs)


def create_predictor(config: Config) -> Predictor:
    """ref: paddle.inference.create_predictor."""
    return Predictor(config)


class _MicroBatcher:
    """Request micro-batching for the predictor server (ref: the
    reference predictor's multi-stream batched serving,
    inference/api/analysis_predictor.h): concurrent requests arriving
    within a short window whose inputs share trailing shapes/dtypes are
    concatenated along axis 0, run as ONE compiled forward, and split
    back — one dispatch serves many clients. Requests that can't batch
    (different signature, outputs not row-aligned) fall back to
    individual runs."""

    def __init__(self, predictor, max_batch: int = 32,
                 window_ms: float = 2.0):
        import queue
        import threading
        self._p = predictor
        self.max_batch = max(int(max_batch), 1)
        self.window_s = max(float(window_ms), 0.0) / 1e3
        self._q: "queue.Queue" = queue.Queue()
        self.batches_run = 0       # introspection / tests
        self.requests_served = 0
        # signatures whose batched run failed once (e.g. fixed-shape AOT
        # executables): don't re-attempt the doomed concatenation every
        # window
        self._no_batch: set = set()
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()

    def run(self, inputs):
        import threading
        done = threading.Event()
        slot: dict = {}
        self._q.put((inputs, done, slot))
        done.wait()
        if "error" in slot:
            raise slot["error"]
        return slot["outs"]

    @staticmethod
    def _sig(inputs):
        return tuple((np.asarray(a).shape[1:], str(np.asarray(a).dtype))
                     for a in inputs)

    def _loop(self):
        import queue
        import time as _time
        while True:
            first = self._q.get()
            batch = [first]
            deadline = _time.monotonic() + self.window_s
            while len(batch) < self.max_batch:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    break
                try:
                    batch.append(self._q.get(timeout=remaining))
                except queue.Empty:
                    break
            # No exception may kill this singleton daemon thread — that
            # would hang every subsequent serve() request forever. _sig
            # failures (malformed inputs) are isolated per REQUEST so
            # one bad client doesn't fail the well-formed requests that
            # share its window; _run_group failures fail that group.
            groups: dict = {}
            for item in batch:
                try:
                    groups.setdefault(self._sig(item[0]), []).append(item)
                except Exception as e:
                    self._fail(item, e)
            for sig, members in groups.items():
                try:
                    self._run_group(sig, members)
                except Exception as e:
                    for m in members:
                        self._fail(m, e)

    @staticmethod
    def _fail(item, e):
        # store the ORIGINAL exception (matching _run_single) so callers
        # see the same type whether the failure hit the batched or the
        # singleton path
        _, done, slot = item
        if not done.is_set():
            slot.setdefault("error", e)
            done.set()

    @staticmethod
    def _bucket(total: int) -> int:
        """Pad totals up to a power of two: arbitrary concatenated row
        counts would each compile a fresh XLA program (and stall every
        queued request behind the compile); bucketing bounds the
        distinct compiled shapes to ~log2(max total)."""
        b = 1
        while b < total:
            b *= 2
        return b

    def _run_group(self, sig, members):
        if len(members) == 1 or sig in self._no_batch:
            for m in members:
                self._run_single(m)
            return
        try:
            rows = [int(np.asarray(m[0][0]).shape[0]) for m in members]
            total = sum(rows)
            padded = self._bucket(total)
            stacked = []
            for i in range(len(members[0][0])):
                arr = np.concatenate(
                    [np.asarray(m[0][i]) for m in members], axis=0)
                if padded > total:
                    pad = np.repeat(arr[-1:], padded - total, axis=0)
                    arr = np.concatenate([arr, pad], axis=0)
                stacked.append(arr)
            outs = self._p.run(*stacked)
            if not all(np.asarray(o).shape[:1] == (padded,)
                       for o in outs):
                raise ValueError("outputs not row-aligned with inputs")
            off = 0
            self.batches_run += 1
            for m, r in zip(members, rows):
                m[2]["outs"] = [np.asarray(o)[off:off + r] for o in outs]
                self.requests_served += 1
                m[1].set()
                off += r
        except Exception:
            # batching invalid for this model/signature (e.g. an AOT
            # artifact's fixed input shape): remember and serve each
            # request on its own from now on
            self._no_batch.add(sig)
            for m in members:
                self._run_single(m)

    def _run_single(self, item):
        inputs, done, slot = item
        try:
            slot["outs"] = [np.asarray(o) for o in self._p.run(*inputs)]
            self.batches_run += 1
            self.requests_served += 1
        except Exception as e:  # noqa: BLE001 — surfaced to the client
            slot["error"] = e
        done.set()


def serve(model_path: str, host: str = "127.0.0.1", port: int = 8866,
          block: bool = True, max_batch: int = 32,
          batch_window_ms: float = 2.0, generate: bool = False,
          max_slots: int = 4, max_seq: int = 256, int8: bool = False,
          eos_id=None, speculative: bool = False,
          spec_tokens: Optional[int] = None,
          spec_draft_layers: Optional[int] = None,
          warm_bundle=None, supervised: bool = False,
          fleet: int = 0):
    """Minimal predictor server (ref: the reference ships its predictor
    behind paddle_serving / the C API server loop; this is the
    batteries-included analog). Concurrent requests are micro-batched
    into one compiled forward (see _MicroBatcher); ``max_batch=1``
    disables batching.

    Protocol: POST /run with an .npz body holding arrays input_0..N;
    response is an .npz of output_0..M. GET /health returns 200.
    Returns the HTTPServer (started in a daemon thread) when block=False.

    ``generate=True`` additionally serves POST /generate for causal-LM
    artifacts: body is an .npz with ``input_ids`` [L] and scalar
    ``max_new_tokens``; response is ``output_ids`` (the generated
    continuation). Requests share the PAGED decode engine's slots with
    iteration-level continuous batching over a shared KV block pool —
    a long generation never blocks a short one, a long PROMPT only
    stalls the batch one prefill chunk at a time, and KV HBM scales
    with active tokens (see serving.PagedLlamaDecodeEngine +
    GenerationServer); ``int8=True`` runs the projections as real s8
    matmuls. ``speculative=True`` additionally attaches a
    truncated-layer draft (``spec_draft_layers`` layers, weights
    shared with the target) proposing ``spec_tokens``
    (default ``FLAGS_serving_spec_tokens``) tokens per step — greedy
    output stays bit-equal, decode steps commit up to the whole
    accepted window per host round-trip.

    ``warm_bundle`` (a manifest path or loaded bundle dict; default
    ``FLAGS_warmup_bundle``) pre-warms the decode/prefill/spec
    executables against the persistent executable cache
    (``FLAGS_executable_cache_dir``) BEFORE the server admits its
    first request — a freshly rolled replica is 100%-cache-hit on its
    first token instead of paying a compile storm under traffic.

    ``supervised=True`` attaches a
    ``serving_supervisor.ServingSupervisor`` to the generation
    server: a decode-loop crash (or stall, with
    ``FLAGS_serving_supervisor_stall_seconds`` set) auto-dumps
    flight, restarts the loop with bounded backoff, and RESUMES
    in-flight generations bit-equal from their committed tokens —
    repeat-offender requests are quarantined instead of crash-looping
    the replica.

    ``fleet=N`` (N >= 2, with ``generate=True``) serves /generate
    through a :class:`serving_fleet.FleetRouter` over N supervised
    replica SUBPROCESSES instead of one in-process engine: KV-
    pressure-aware placement, failover with bit-equal stream
    recovery, and warm-bundle resurrection of dead replicas (see
    ``serving_fleet``). The replicas share this process's executable
    cache directory and ``warm_bundle``, so a recycled replica rejoins
    without a compile storm. The router process loads no model (on a
    TPU host each replica child claims its own chip), so a fleet
    serves /generate only and POST /run answers 404.
    """
    import io
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from .core.flags import flag_value
    from .jit import warmup as _warmup
    _warmup.ensure_executable_cache()
    batcher = None
    gen_server = None
    fleet_router = None
    if warm_bundle is None:
        warm_bundle = flag_value("warmup_bundle") or None
    if generate and int(fleet) >= 2:
        # the router process loads no model: a chip belongs to one
        # process, and each replica child claims its own (a Predictor
        # here would initialise the backend and hold them all). The
        # fleet therefore serves /generate only.
        from .serving_fleet import spawn_fleet
        fleet_router = spawn_fleet(int(fleet), {
            "model": {"kind": "inference_model", "path": model_path},
            "max_slots": max_slots, "max_seq": max_seq, "int8": int8,
            "eos_id": eos_id, "warm_bundle": warm_bundle,
            "supervised": True})
    else:
        predictor = Predictor(Config(model_path))
        batcher = _MicroBatcher(predictor, max_batch=max_batch,
                                window_ms=batch_window_ms)
    if generate and fleet_router is None:
        from .serving import GenerationServer, PagedLlamaDecodeEngine
        # reuse the predictor's already-loaded Layer (a second
        # load_inference_model would hold the weights twice at startup)
        model = predictor.model if predictor.model is not None \
            else load_inference_model(model_path)
        engine = PagedLlamaDecodeEngine(
            model, max_slots=max_slots, max_seq=max_seq, int8=int8,
            eos_id=eos_id)
        if speculative:
            engine.attach_draft(
                engine.make_draft(model, num_layers=spec_draft_layers),
                spec_tokens=spec_tokens)
        if warm_bundle:
            # pre-warm BEFORE the loop thread starts admitting: the
            # first request's decode/prefill steps must be cache hits
            _warmup.prewarm(warm_bundle, engine=engine)
        gen_server = GenerationServer(engine)
        if supervised:
            from .serving_supervisor import supervise
            # held on the server so the monitor lives exactly as long
            # as the serving process does
            gen_server._supervisor = supervise(gen_server)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet
            pass

        def do_GET(self):
            if self.path == "/health":
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"ok")
            else:
                self.send_response(404)
                self.end_headers()

        def do_POST(self):
            if self.path not in ("/run", "/generate"):
                self.send_response(404)
                self.end_headers()
                return
            if self.path == "/generate" and gen_server is None \
                    and fleet_router is None:
                msg = b"serve(generate=True) not enabled"
            elif self.path == "/run" and batcher is None:
                msg = b"serve(fleet=N) serves /generate only"
            else:
                msg = None
            if msg is not None:
                self.send_response(404)
                self.send_header("Content-Length", str(len(msg)))
                self.end_headers()
                self.wfile.write(msg)
                return
            try:
                n = int(self.headers.get("Content-Length", "0"))
                data = np.load(io.BytesIO(self.rfile.read(n)),
                               allow_pickle=False)
                if self.path == "/generate":
                    ids = np.asarray(data["input_ids"]).reshape(-1)
                    mnt = int(data["max_new_tokens"]) \
                        if "max_new_tokens" in data else 32
                    toks = (fleet_router or gen_server).generate(
                        ids, mnt)
                    outs = [np.asarray(toks, np.int32)]
                    buf = io.BytesIO()
                    np.savez(buf, output_ids=outs[0])
                    body = buf.getvalue()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/npz")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                inputs = [data[f"input_{i}"] for i in range(len(data))]
                outs = batcher.run(inputs)
                buf = io.BytesIO()
                np.savez(buf, **{f"output_{i}": o
                                 for i, o in enumerate(outs)})
                body = buf.getvalue()
                self.send_response(200)
                self.send_header("Content-Type", "application/npz")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            except Exception as e:  # surface the error to the client
                msg = repr(e).encode()
                self.send_response(500)
                self.send_header("Content-Length", str(len(msg)))
                self.end_headers()
                self.wfile.write(msg)

    server = ThreadingHTTPServer((host, port), Handler)
    server.batcher = batcher  # introspection (tests, metrics)
    server.gen_server = gen_server
    server.fleet_router = fleet_router
    if block:
        server.serve_forever()
        return None
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server
