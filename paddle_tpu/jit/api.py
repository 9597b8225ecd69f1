"""Functionalization + compiled train/eval steps.

Core mechanism: a Layer's Parameters/buffers are leaf Tensors; swapping
their ``._data`` for JAX tracers and calling ``forward`` traces the same
Python code into an XLA program. Gradients come from ``jax.value_and_grad``
over the functionalized program, and the optimizer's pure per-param
``_update`` runs inside the same compiled step (one fused XLA executable for
fwd+bwd+opt, the shape the TPU wants).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as random_mod
from ..core.autograd import no_grad
from ..core.tensor import Parameter, Tensor


# Analysis-auditor hook (paddle_tpu.analysis.auditor): notified with
# (kind,) each time a whole-step program is (re)built — a steady-state
# training loop should build exactly once, so builds inside an audit's
# measured window are recompile churn. None outside an audit.
_build_observer = None


def _notify_build(kind: str) -> None:
    from ..observability import flight as _flight
    from .warmup import ensure_executable_cache
    # every whole-step (re)build is about to jit-compile: make sure the
    # persistent executable cache is configured first (builds are rare)
    ensure_executable_cache()
    _flight.record("jit", "build", kind=kind)
    obs = _build_observer
    if obs is not None:
        obs(kind)


class InputSpec:
    """ref: python/paddle/static/input.py InputSpec"""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = shape
        self.dtype = dtype
        self.name = name


def _tree_unwrap(x):
    if isinstance(x, Tensor):
        return x._data
    if isinstance(x, (list, tuple)):
        return type(x)(_tree_unwrap(v) for v in x)
    if isinstance(x, dict):
        return {k: _tree_unwrap(v) for k, v in x.items()}
    return x


def _tree_wrap(x):
    if isinstance(x, (jax.Array,)) or hasattr(x, "aval"):
        return Tensor(x)
    if isinstance(x, (list, tuple)):
        return type(x)(_tree_wrap(v) for v in x)
    if isinstance(x, dict):
        return {k: _tree_wrap(v) for k, v in x.items()}
    return x


class _Swap:
    """Temporarily install pytree values into a layer's param/buffer
    Tensors; capture buffer mutations (e.g. BN running stats) on exit."""

    def __init__(self, layer):
        self.params = dict(layer.named_parameters())
        self.buffers = dict(layer.named_buffers())

    def run(self, param_vals: Dict[str, Any], buffer_vals: Dict[str, Any],
            fn, *args, **kwargs):
        old_p = {k: t._data for k, t in self.params.items()}
        old_b = {k: t._data for k, t in self.buffers.items()}
        try:
            for k, t in self.params.items():
                t._data = param_vals[k]
            for k, t in self.buffers.items():
                if k in buffer_vals:
                    t._data = buffer_vals[k]
            out = fn(*args, **kwargs)
            new_buffers = {k: t._data for k, t in self.buffers.items()}
            return out, new_buffers
        finally:
            for k, t in self.params.items():
                t._data = old_p[k]
            for k, t in self.buffers.items():
                t._data = old_b[k]


def functionalize(layer, fn: Optional[Callable] = None):
    """Returns (apply, params, buffers):
    apply(params, buffers, *args, **kwargs) -> (out_pytree, new_buffers)
    pure in its inputs; params/buffers are {name: jnp array} pytrees."""
    swap = _Swap(layer)
    call = fn if fn is not None else layer.__call__
    params0 = {k: t._data for k, t in swap.params.items()}
    buffers0 = {k: t._data for k, t in swap.buffers.items()}

    def apply(params, buffers, *args, **kwargs):
        with no_grad():
            args_t = tuple(Tensor(a) if _is_arr(a) else a for a in args)
            kwargs_t = {k: (Tensor(v) if _is_arr(v) else v)
                        for k, v in kwargs.items()}
            out, new_buffers = swap.run(params, buffers, call, *args_t,
                                        **kwargs_t)
            return _tree_unwrap(out), new_buffers

    return apply, params0, buffers0


def _is_arr(v):
    return isinstance(v, (jax.Array, np.ndarray)) or hasattr(v, "aval")


class StaticFunction:
    """Result of to_static on a layer/function: jit-compiled forward with a
    shape/dtype-keyed compile cache (jax.jit's own cache)."""

    def __init__(self, layer_or_fn, input_spec=None, **kwargs):
        from ..nn.layer import Layer
        self._is_layer = isinstance(layer_or_fn, Layer)
        if self._is_layer:
            self._layer = layer_or_fn
            self._fn = layer_or_fn.__call__
        else:
            self._layer = getattr(layer_or_fn, "__self__", None)
            self._fn = layer_or_fn
        self.input_spec = input_spec
        self._jitted = None

    def _build(self):
        _notify_build("static_function")
        if self._layer is not None:
            apply, _, _ = functionalize(self._layer, self._fn)

            @functools.partial(jax.jit)
            def jitted(params, buffers, key, *args, **kwargs):
                with random_mod.key_stream(key):
                    out, new_buffers = apply(params, buffers, *args,
                                             **kwargs)
                return out, new_buffers
            self._jitted = jitted
            self._swap = _Swap(self._layer)
        else:
            fn = self._fn

            @functools.partial(jax.jit)
            def jitted(key, *args, **kwargs):
                with random_mod.key_stream(key), no_grad():
                    args_t = tuple(Tensor(a) if _is_arr(a) else a
                                   for a in args)
                    out = fn(*args_t, **kwargs)
                return _tree_unwrap(out)
            self._jitted = jitted

    def __call__(self, *args, **kwargs):
        if self._jitted is None:
            self._build()
        raw_args = tuple(_tree_unwrap(a) for a in args)
        raw_kwargs = {k: _tree_unwrap(v) for k, v in kwargs.items()}
        key = random_mod.next_key()
        if self._layer is not None:
            params = {k: t._data for k, t in self._swap.params.items()}
            buffers = {k: t._data for k, t in self._swap.buffers.items()}
            out, new_buffers = self._jitted(params, buffers, key, *raw_args,
                                            **raw_kwargs)
            for k, t in self._swap.buffers.items():
                t._data = new_buffers[k]
            return _tree_wrap(out)
        out = self._jitted(key, *raw_args, **raw_kwargs)
        return _tree_wrap(out)


_to_static_enabled = True


def enable_to_static(flag: bool):
    """ref: jit/api.py enable_to_static — global kill-switch: with False,
    to_static returns the function/layer untouched (pure eager), the
    reference's debugging workflow for dy2static issues."""
    global _to_static_enabled
    _to_static_enabled = bool(flag)


_D2S_LOGGER_NAME = "paddle_tpu.jit.dy2static"


def set_verbosity(level: int = 0, also_to_stdout: bool = False):
    """ref: jit/dy2static/logging_utils.py set_verbosity — verbosity of
    the dy2static/SOT transform logs (0 silences, higher = chattier)."""
    import logging
    logger = logging.getLogger(_D2S_LOGGER_NAME)
    logger.setLevel(logging.WARNING if level <= 0 else
                    logging.INFO if level == 1 else logging.DEBUG)
    if also_to_stdout and not logger.handlers:
        import sys
        logger.addHandler(logging.StreamHandler(sys.stdout))


def set_code_level(level: int = 100, also_to_stdout: bool = False):
    """ref: jit/dy2static/logging_utils.py set_code_level — how much
    transformed code to log. The SOT tracer has no source transform to
    print; at level>0 it logs each compiled trace's op count through the
    same logger (the observable analog)."""
    import logging
    logger = logging.getLogger(_D2S_LOGGER_NAME + ".code")
    logger.setLevel(logging.DEBUG if level > 0 else logging.WARNING)
    if also_to_stdout and not logger.handlers:
        import sys
        logger.addHandler(logging.StreamHandler(sys.stdout))


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, bucket_policy=None, **kwargs):
    """ref: python/paddle/jit/api.py to_static.

    full_graph=False (default, the reference's SOT mode): op-level tracer
    with graph breaks — data-dependent Python control flow works; breaks
    become guards, paths replay compiled, non-replayable traces (RNG /
    in-place mutation / inner backward) fall back to eager
    (see paddle_tpu.jit.sot).

    full_graph=True (the reference's AST mode): whole-program jax.jit —
    fastest when the function is fully traceable (no data-dependent
    control flow), with proper functionalization of Layer params/buffers
    and RNG.
    """
    def decorate(fn):
        if not _to_static_enabled:
            return fn
        if full_graph:
            return StaticFunction(fn, input_spec, **kwargs)
        from .sot import SOTFunction
        from ..nn.layer import Layer
        if isinstance(fn, Layer):
            # patch forward in place so the object keeps its Layer API
            # (parameters/train/eval/state_dict, jit.save) — the
            # reference's to_static(layer) likewise returns the layer
            # with a StaticFunction forward
            sot = SOTFunction(fn.forward, bucket_policy=bucket_policy,
                              input_spec=input_spec)
            fn.forward = sot
            return fn
        return SOTFunction(fn, bucket_policy=bucket_policy,
                           input_spec=input_spec)
    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn=None):
    return fn


def ignore_module(modules):
    return None


class TrainStep:
    """Whole-training-step compiler: loss fwd + backward + optimizer update
    as ONE XLA executable (donated params/opt-state, so updates are
    in-place in HBM).

    Usage:
        step = TrainStep(model, loss_fn, optimizer)
        loss = step(x, y)          # tensors or numpy

    loss_fn(outputs, *labels) -> scalar Tensor.

    Since Fusion III this is a thin wrapper over the SOT whole-step
    capture engine (``jit.sot.CapturedStep`` in non-strict mode: an
    EXPLICIT whole-step API, so it always captures — no eager fallback,
    no kill switch, unknown clip objects run un-clipped inside the
    trace as before). ``hapi.Model.train_batch`` rides the same
    machinery in strict mode (gated, compile-on-second-sighting).
    Optimizer slot state now lives in ``optimizer._states`` (shared
    with the eager/fused paths), so ``state_dict()`` round-trips cover
    compiled training too.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True):
        from .sot import CapturedStep
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._step = CapturedStep(
            model, loss_fn, optimizer, cast_loss_f32=True,
            donate=donate, strict=False, name="train_step",
            build_kind="train_step")

    @staticmethod
    def _split(batch):
        if len(batch) > 1:
            return list(batch[:-1]), [batch[-1]]
        return list(batch), []

    def compile_stats(self, *batch):
        """Compile the step for these batch shapes without running it and
        return XLA's per-device memory analysis (same contract as
        DistTrainStep.compile_stats)."""
        ins, lbls = self._split(batch)
        return self._step.compile_stats(ins, lbls)

    def __call__(self, *batch):
        ins, lbls = self._split(batch)
        return self._step.step(ins, lbls)


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save — persists params + the importable factory so load
    reconstructs a runnable Layer (ref: python/paddle/jit/api.py save /
    TranslatedLayer). Shares the .pdmodel format with
    paddle_tpu.inference.save_inference_model."""
    from ..inference import save_inference_model
    save_inference_model(path, layer, input_spec=input_spec)


class TranslatedLayer:
    """ref: jit/translated_layer.py TranslatedLayer — the Layer-like
    object jit.load returns when the saved model's Python class cannot
    be imported in this process: forward runs the artifact's
    AOT-exported (StableHLO) program with the saved params/buffers.
    Built lazily over inference.Predictor's AOT path; construction is
    via TranslatedLayer.load (or jit.load's fallback), matching the
    reference's 'not created by constructor' contract."""

    def __init__(self, predictor):
        self._predictor = predictor
        self.training = False

    @staticmethod
    def load(path):
        from ..inference import Config, Predictor
        return TranslatedLayer(Predictor(Config(path)))

    def forward(self, *inputs):
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        outs = self._predictor.run(*inputs)
        outs = [Tensor(jnp.asarray(o)) for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def __call__(self, *inputs):
        return self.forward(*inputs)

    def eval(self):
        self.training = False
        return self

    def train(self):
        raise RuntimeError(
            "TranslatedLayer wraps a compiled inference program; it "
            "cannot be put in train mode (re-train from the original "
            "Layer class)")


def load(path, **configs):
    """Returns a reconstructed Layer in eval mode (ref: jit.load →
    TranslatedLayer). If the artifact carries an AOT export and the
    original class is NOT importable here, a TranslatedLayer serves it
    instead. Legacy .pdparams artifacts (raw state-dicts, not
    reconstructable Layers) fail loudly with the right tool named."""
    import os

    from ..inference import load_inference_model
    if not os.path.exists(path + ".pdmodel") and \
            os.path.exists(path + ".pdparams"):
        raise ValueError(
            f"{path}.pdparams is a legacy weights-only artifact and "
            "cannot be reconstructed into a Layer; load it with "
            "paddle_tpu.load() and apply set_state_dict on your model")
    try:
        return load_inference_model(path)
    except (ImportError, AttributeError, ModuleNotFoundError) as e:
        from ..inference import _load
        payload = _load(path + ".pdmodel", return_numpy=False)
        if payload.get("aot"):
            return TranslatedLayer.load(path)
        raise ValueError(
            f"cannot reconstruct {payload.get('class_name')} ({e}) and "
            f"the artifact has no AOT export — re-save with "
            f"save_inference_model(aot=True) to serve without the "
            f"class") from e
