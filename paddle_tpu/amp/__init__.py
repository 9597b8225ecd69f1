"""paddle.amp equivalent. ref: python/paddle/amp/__init__.py"""
from .auto_cast import auto_cast, autocast, decorate, amp_guard, white_list  # noqa: F401
from .grad_scaler import GradScaler  # noqa: F401


def is_float16_supported(device=None) -> bool:
    """ref: amp/__init__.py is_float16_supported. TPUs execute fp16
    arithmetic but have no fp16 MXU advantage — supported, not native."""
    import jax
    return jax.default_backend() in ("tpu", "gpu")


def is_bfloat16_supported(device=None) -> bool:
    """ref: amp/__init__.py is_bfloat16_supported. bf16 is the TPU's
    native fast dtype."""
    return True
