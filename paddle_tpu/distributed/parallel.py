"""Parallel environment bootstrap + DataParallel.

ref: python/paddle/distributed/parallel.py:978 (init_parallel_env),
:396-419 (DataParallel over EagerReducer bucketed allreduce,
ref: paddle/fluid/distributed/collective/reducer.cc). TPU-native:
bootstrap is jax.distributed.initialize (PJRT coordination service plays
the TCPStore role, ref: phi/core/distributed/store/tcp_store.h:121);
DataParallel's gradient sync is an allreduce over the dp group after
backward — on a single controller the preferred path is instead batch
sharding via shard_tensor/pjit, which needs no wrapper at all.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

from ..core.tensor import Tensor
from ..nn.layer import Layer
from .collective import (Group, ReduceOp, _ensure_default_group, all_reduce,
                         _global_rank, _world_size)

__all__ = [
    "init_parallel_env", "get_rank", "get_world_size", "is_initialized",
    "ParallelEnv", "DataParallel",
]

_initialized = False


def init_parallel_env() -> Group:
    """ref: parallel.py:978. Reads PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
    PADDLE_MASTER (set by paddle_tpu.distributed.launch) and brings up the
    JAX distributed runtime; single-process when unset."""
    global _initialized
    if _initialized:
        return _ensure_default_group()
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    nranks = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    master = os.environ.get("PADDLE_MASTER",
                            os.environ.get("MASTER_ADDR", ""))
    # probe WITHOUT touching the backend: jax.process_count() would
    # initialize XLA right here, making the initialize() below a
    # guaranteed too-late failure (silent store-transport fallback)
    if nranks > 1 and not jax.distributed.is_initialized():
        port = os.environ.get("MASTER_PORT", "")
        addr = master if ":" in master or not port else f"{master}:{port}"
        try:
            # CPU backend: cross-process collectives need a real CPU
            # collectives implementation (gloo) — the analog of the
            # reference picking ProcessGroupGloo for CPU places
            # (ref: parallel.py:978 _new_process_group_impl backend map)
            if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=addr, num_processes=nranks,
                process_id=rank)
        except RuntimeError as e:
            if "must be called before" not in str(e):
                raise  # genuine bootstrap failure (bad address etc.)
            # XLA backend already up (e.g. the import touched jax.devices,
            # or the CPU test harness): eager collectives fall back to the
            # TCPStore channel transport — ranks come from the launcher env.
    _initialized = True
    return _ensure_default_group()


def is_initialized() -> bool:
    return _initialized


def get_rank(group: Optional[Group] = None) -> int:
    if group is not None:
        return group.rank
    return _global_rank()


def get_world_size(group: Optional[Group] = None) -> int:
    if group is not None:
        return group.nranks
    return _world_size()


class ParallelEnv:
    """ref: parallel.py ParallelEnv (env introspection object)."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def dev_id(self):
        return int(os.environ.get("FLAGS_selected_tpus", "0"))

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def trainer_endpoints(self):
        return os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")


class DataParallel(Layer):
    """ref: parallel.py:396 DataParallel. Gradient allreduce over the dp
    group after backward with size-bucketed FUSION (ref: EagerReducer,
    fluid/distributed/collective/reducer.cc Eager_AssignGroupBySize +
    FusedAllReduceSchedule): grads are packed into ~comm_buffer_size-MB
    flat buffers so the eager path issues one collective per bucket —
    over the store transport that's one round-trip per bucket instead of
    one per parameter; in compiled steps XLA's collective combiner plays
    this role."""

    def __init__(self, layers: Layer, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group: Optional[Group] = None):
        super().__init__()
        self._layers = layers
        self._group = group
        self.comm_buffer_size = comm_buffer_size
        self.last_comm_buffer_size = last_comm_buffer_size
        self.find_unused_parameters = find_unused_parameters
        init_parallel_env()

    def _grad_buckets(self):
        """Group parameters by accumulated byte size (ref: reducer.h:41
        Eager_AssignGroupBySize with group limits [last_comm_buffer_size,
        comm_buffer_size] — the first bucket stays small so its fused
        allreduce launches early). Buckets cover EVERY trainable param in
        a deterministic order — a rank whose control flow skipped some
        param contributes zeros rather than shifting the flat layout
        (rank-divergent layouts would sum unrelated slices together)."""
        first_limit = max(int(self.last_comm_buffer_size), 1) * 1024 * 1024
        limit = max(int(self.comm_buffer_size), 1) * 1024 * 1024
        buckets = []
        cur, cur_bytes, cur_dtype = [], 0, None
        for p in self._layers.parameters():
            if p.stop_gradient:
                continue
            nbytes = p._data.nbytes
            cap = first_limit if not buckets else limit
            if cur and (cur_bytes + nbytes > cap or
                        p._data.dtype != cur_dtype):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(p)
            cur_bytes += nbytes
            cur_dtype = p._data.dtype
        if cur:
            buckets.append(cur)
        return buckets

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)

    def apply_collective_grads(self):
        """ref: hybrid_parallel_util.py fused_allreduce_gradients +
        reducer.cc FusedAllReduceSchedule — one flat AVG allreduce per
        size bucket, then unpack back into each param's grad. Params with
        no local grad contribute zeros (keeps the flat layout identical
        on every rank) and do not get a grad written back."""
        import jax.numpy as jnp

        n = get_world_size(self._group)
        if n <= 1:
            return
        for bucket in self._grad_buckets():
            # every rank joins every bucket's collective, even with no
            # local grads (zeros) — skipping would desequence the store
            # transport / deadlock the ring on ranks that do have grads
            # the flat layout is bucketed by PARAM dtype (deterministic
            # across ranks even when some rank has no grad); a grad whose
            # dtype differs (e.g. fp32 grads on bf16 params) is packed in
            # the param dtype and restored to its own dtype after — never
            # let jnp.concatenate promote the whole buffer
            if len(bucket) == 1:
                p = bucket[0]
                if p.grad is None:
                    all_reduce(Tensor(jnp.zeros_like(p._data)),
                               ReduceOp.AVG, self._group)
                elif p.grad._data.dtype == p._data.dtype:
                    all_reduce(p.grad, ReduceOp.AVG, self._group)
                else:
                    gdt = p.grad._data.dtype
                    t = Tensor(p.grad._data.astype(p._data.dtype))
                    all_reduce(t, ReduceOp.AVG, self._group)
                    p.grad._data = t._data.astype(gdt)
                continue
            flat = jnp.concatenate([
                (p.grad._data.astype(p._data.dtype)
                 if p.grad is not None
                 else jnp.zeros_like(p._data)).reshape(-1)
                for p in bucket])
            fused = Tensor(flat)
            all_reduce(fused, ReduceOp.AVG, self._group)
            off = 0
            for p in bucket:
                size = p._data.size
                if p.grad is not None:
                    p.grad._data = fused._data[off:off + size].astype(
                        p.grad._data.dtype).reshape(p.grad._data.shape)
                off += size

    def scale_loss(self, loss):
        return loss

    def __getattr__(self, name):
        try:
            return super().__getattr__(name)
        except AttributeError:
            return getattr(self._layers, name)
