"""Pallas TPU kernels — the hand-written hot ops.

The analog of the reference's fused kernel zoo (ref: paddle/phi/kernels/
fusion/, 90k LoC CUDA/CUTLASS): flash attention, fused RoPE, fused
layernorm. Each module exposes a jittable function with a custom_vjp and a
pure-XLA fallback for non-TPU backends (used by the CPU test mesh).
"""
from ...observability import metrics as _om

_M_path = _om.counter(
    "pallas.path_selected_total",
    "Selections at each Pallas seam by kernel and path taken (the Mosaic "
    "kernel vs its XLA/jnp reference), counted where the choice is made: "
    "once per trace under jit, so a compiled step counts once however "
    "often it runs")


def count_path(kernel: str, path: str) -> None:
    """Record which implementation a kernel seam chose. The choice is by
    platform and shape, never silent: every seam calls this."""
    _M_path.inc(kernel=kernel, path=path)


from . import flash_attention  # noqa: E402,F401
from . import paged_attention  # noqa: E402,F401
