"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
PaddlePaddle (the reference at /root/reference), built on JAX/XLA/Pallas.

Top-level namespace mirrors `paddle.*` (ref: python/paddle/__init__.py):
tensor creation/math/manipulation/linalg ops, nn, optimizer, io, amp,
distributed, jit, vision.
"""
from __future__ import annotations

__version__ = "0.1.0"

import warnings as _warnings

import jax as _jax

# fp32 matmuls accumulate in full precision by default (the reference's cuBLAS
# fp32 GEMMs do); bf16 inputs still ride the MXU at full rate. Perf-sensitive
# code paths opt into lower precision per-call via jax.default_matmul_precision.
_jax.config.update("jax_default_matmul_precision", "float32")

# TPU/XLA runs with 32-bit index types by default (jax x64 disabled); the
# paddle-style API nominally uses int64 indices, which JAX silently narrows.
_warnings.filterwarnings(
    "ignore", message="Explicitly requested dtype int64")
_warnings.filterwarnings(
    "ignore", message="Explicitly requested dtype float64")

# dtypes
from .core.dtype import (  # noqa: F401
    bool_ as bool8, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128,
    get_default_dtype, set_default_dtype,
)
from .core import dtype as dtype_module  # noqa: F401
from .core.dtype import bool_  # noqa: F401

# core tensor + autograd
from .core import fusion  # noqa: F401  (paddle.fusion.stats() surface)
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.autograd import no_grad, enable_grad, is_grad_enabled, grad  # noqa: F401
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .core.device import (  # noqa: F401
    set_device, get_device, device_count, is_compiled_with_cuda,
    is_compiled_with_tpu, CPUPlace, TPUPlace, Place,
)

# functional ops (also patches Tensor methods)
from .ops import *  # noqa: F401,F403
from .ops import cast, increment  # noqa: F401

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import amp  # noqa: F401
from . import autograd  # noqa: F401
from . import jit  # noqa: F401
from . import distributed  # noqa: F401
from . import vision  # noqa: F401
from . import metric  # noqa: F401
from . import linalg  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import quantization  # noqa: F401
from . import regularizer  # noqa: F401
from . import incubate  # noqa: F401
from . import models  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import utils  # noqa: F401
from . import observability  # noqa: F401  (unified telemetry runtime)
from . import inference  # noqa: F401
# paddle_tpu.profiler is not re-exported here: users import it explicitly
# (matching `import paddle.profiler` usage). It is loaded all the same, by
# the modules whose spans are its RecordEvent (jit.sot, serving).
from .framework.io import save, load  # noqa: F401
from .hapi.model import Model, flops, summary  # noqa: F401
from . import callbacks  # noqa: F401

from .ops import inplace as _inplace_ops  # noqa: F401  (installs op_ variants)
from . import static  # noqa: F401
from . import geometric  # noqa: F401
from . import device as device  # noqa: F401
from . import hub  # noqa: F401
from . import onnx  # noqa: F401

# hot start: the persistent executable cache is configured BEFORE any
# compile (model-init jnp programs included); the compile seams re-check
# on runtime set_flags. Where it lives: jit.warmup.cache_dir()
jit.warmup.ensure_executable_cache()


def disable_static(place=None):
    """Back to eager (the default). ref: paddle.disable_static."""
    from .static.program import _set_static_mode
    _set_static_mode(False)


def enable_static():
    """Record subsequent ops into static.default_main_program(); run them
    with static.Executor. ref: paddle.enable_static (SURVEY layer 14)."""
    from .static.program import _set_static_mode
    _set_static_mode(True)


def in_dynamic_mode():
    from .static.program import _static_mode
    return not _static_mode()


# ---------------------------------------------------------------------------
# misc top-level parity (ref: python/paddle/__init__.py __all__ tail)
# ---------------------------------------------------------------------------
def iinfo(dtype):
    """ref: paddle.iinfo — integer type info."""
    from .core.dtype import convert_dtype as _cd
    return np.iinfo(np.dtype(str(jnp.dtype(_cd(dtype)))))


def finfo(dtype):
    """ref: paddle.finfo — float type info."""
    from .core.dtype import convert_dtype as _cd
    return jnp.finfo(jnp.dtype(_cd(dtype)))


dtype = jnp.dtype

from .distributed.parallel import DataParallel  # noqa: F401,E402


class CUDAPlace(Place):  # noqa: F405  (accepted alias; executes on TPU)
    def __init__(self, device_id=0):
        super().__init__("gpu", device_id)


class CUDAPinnedPlace(Place):  # noqa: F405
    def __init__(self):
        super().__init__("gpu_pinned", 0)


class LazyGuard:
    """ref: paddle.LazyGuard — deferred parameter init. Parameters here
    are cheap jax arrays, so the guard is a no-op context."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """ref: paddle.create_parameter."""
    from .nn import initializer as I
    init = default_initializer or (I.Constant(0.0) if is_bias
                                   else I.XavierNormal())
    from .core.dtype import convert_dtype as _cd
    data = init(tuple(shape), _cd(dtype))
    p = Parameter(data)
    if name:
        p.name = name
    return p


def rank(x):
    """ref: paddle.rank — number of dimensions as a 0-D tensor."""
    return to_tensor(np.asarray((x._data if isinstance(x, Tensor)
                                 else np.asarray(x)).ndim))  # noqa: F405


def shape(x):
    """ref: paddle.shape — runtime shape as an int tensor."""
    return to_tensor(np.asarray(  # noqa: F405
        (x._data if isinstance(x, Tensor) else np.asarray(x)).shape,
        np.int64))


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """ref: paddle.set_printoptions — applies to numpy reprs."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


def set_grad_enabled(mode):
    """ref: paddle.set_grad_enabled (context manager)."""
    from .core.autograd import _GradModeGuard
    return _GradModeGuard(True if mode else False)


def is_compiled_with_cinn():
    return False  # the compiler here is XLA


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def disable_signal_handler():
    return None


def check_shape(x):
    return None  # shapes are static under tracing; nothing to defer


def batch(reader, batch_size, drop_last=False):
    """ref: paddle.batch (legacy reader decorator)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


from .nn import ParamAttr  # noqa: F401,E402

float8_e4m3fn = jnp.float8_e4m3fn
float8_e5m2 = jnp.float8_e5m2


def get_cuda_rng_state():
    """Alias of get_rng_state (accepted for reference compat; the device
    stream is the framework generator)."""
    return get_rng_state()  # noqa: F405


def set_cuda_rng_state(state):
    return set_rng_state(state)  # noqa: F405


def binomial(count, prob, name=None):
    """ref: paddle.binomial — draws with per-element counts/probs."""
    from .core import random as _rnd
    import jax as _jax
    key = _rnd.next_key()
    from .core.autograd import apply_op as _apply
    return _apply(lambda n, q: _jax.random.binomial(
        key, n, q).astype(jnp.int64), count, prob, op_name="binomial")


def _toplevel_inplace(name):
    def f(x, *args, **kwargs):
        return getattr(x, name)(*args, **kwargs)
    f.__name__ = name
    return f


# tensor-method inplace forms also exposed at module level
normal_ = _toplevel_inplace("normal_")
log_normal_ = _toplevel_inplace("log_normal_")
bernoulli_ = _toplevel_inplace("bernoulli_")
cauchy_ = _toplevel_inplace("cauchy_")
geometric_ = _toplevel_inplace("geometric_")
divide_ = _toplevel_inplace("divide_")


def addmm_(input, x, y, beta=1.0, alpha=1.0, name=None):
    out = addmm(input, x, y, beta=beta, alpha=alpha)  # noqa: F405
    input._data = out._data
    return input


def where_(condition, x, y, name=None):
    """ref: tensor/search.py:828 where_ — the result lands in x."""
    out = where(condition, x, y)  # noqa: F405
    x._data = out._data
    return x


def tolist(x):
    return x.tolist()


# paddle.bool dtype alias — assigned last so the module body above keeps
# the builtin
bool = bool_  # noqa: F405,A001
