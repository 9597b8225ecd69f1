"""Test harness config.

Runs the whole suite on the XLA CPU backend with 8 virtual devices so that
mesh/sharding/collective logic is exercised without TPU hardware — the
strategy SURVEY.md §4 calls for (the reference's closest analog is the
fake_cpu_device CustomDevice plugin, ref: paddle/phi/backends/custom/
fake_cpu_device.h + test/custom_runtime/).

Env vars must be set before the first jax import, hence this file's top.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: a TPU host would select the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def greedy_ref(model):
    """The serving tests' token reference, over the requesting module's
    own ``model`` fixture: ``(prompt, n_new) -> LlamaForCausalLM.generate``'s
    greedy stream (eager forward passes over concatenated K/V: no engine,
    no block pool, no compiled step), memoized by prompt. A greedy stream
    is prefix-closed, so a shorter request is cut from a longer one."""
    import paddle_tpu as paddle
    streams = {}

    def ref(prompt, n_new):
        key = tuple(int(t) for t in prompt)
        if len(streams.get(key, ())) < n_new:
            ids = paddle.to_tensor(np.asarray(key, np.int32)[None, :])
            full = model.generate(ids, max_new_tokens=int(n_new))
            streams[key] = [int(t) for t in
                            np.asarray(full.numpy())[0, len(key):]]
        return streams[key][:int(n_new)]

    return ref
