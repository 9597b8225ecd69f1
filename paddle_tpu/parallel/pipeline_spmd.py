"""Fully-compiled pipeline parallelism: GPipe schedule inside one jit.

The reference's PP is a host-driven micro-batch loop with NCCL p2p
(ref: fleet/meta_parallel/pipeline_parallel.py:575-720 1F1B,
pp_utils/p2p_communication.py send/recv). On TPU a host loop serializes on
dispatch latency (SURVEY.md §7 hard parts), so this module compiles the
whole schedule: per-stage parameters are STACKED with a leading stage dim
sharded on the 'pp' mesh axis; a lax.fori_loop ticks M + S - 1 times, each
tick running every stage on its in-flight micro-batch and rotating
activations one hop with ppermute (p2p over ICI). Backward is jax.grad
through the loop — autodiff reverses the schedule, giving the cooldown
phase for free.

Stages must be structurally identical (e.g. the decoder-layer stack);
embedding/head run outside the pipelined region, as on stage-0/stage-N
in the reference's PipelineLayer segmentation (ref: pp_layers.py:257).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["spmd_pipeline", "spmd_pipeline_interleaved",
           "stack_layer_params", "remat_policy"]


def remat_policy(name):
    """Resolve a rematerialization policy knob for the pipeline stage body.

    ref-analog: the reference bounds PP activation memory by hand with the
    1F1B schedule (pipeline_parallel.py:575-720) + recompute
    (fleet recompute / auto_parallel_recompute pass). Under whole-program
    autodiff the equivalent lever is jax.checkpoint on the per-tick stage
    computation:
      - "none": save every stage-internal activation (fastest backward,
        highest memory);
      - "dots": save only matmul outputs
        (jax.checkpoint_policies.dots_saveable) — the usual sweet spot;
      - "full": save nothing, recompute the whole stage body in backward
        (jax.checkpoint_policies.nothing_saveable) — activation residuals
        shrink to the one carried activation per tick.
    Memory shape (measured by tests/test_pipeline_memory.py): the
    compiled GPipe schedule stores one carried activation per tick
    (linear in M with a one-activation constant under "full"); the
    host-driven fleet 1F1B path keeps the reference's S-bounded profile
    when M-independence is required.
    """
    if name in (None, "none", False):
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_saveable
    if callable(name):
        return name
    raise ValueError(f"unknown remat policy {name!r}")


def _maybe_remat(stage_fn, remat):
    policy = remat_policy(remat)
    if policy is None:
        return stage_fn
    return jax.checkpoint(stage_fn, policy=policy)


def stack_layer_params(per_layer_params: Sequence[dict]) -> dict:
    """[{name: arr}, ...] for S structurally-identical layers -> one pytree
    {name: arr[S, ...]}; shard its leading dim on the pp axis."""
    keys = list(per_layer_params[0].keys())
    return {k: jnp.stack([p[k] for p in per_layer_params]) for k in keys}


def _pipeline_local(params, microbatches, *, stage_fn, axis):
    """Runs per-stage inside shard_map. params: leading dim 1 (this stage's
    slice); microbatches: [M, B, ...] (replicated input feed)."""
    S = jax.lax.psum(1, axis)
    sid = jax.lax.axis_index(axis)
    M = microbatches.shape[0]
    # each mesh stage may hold several consecutive layers (stacked dim //
    # axis size); it runs them back-to-back per tick
    group = next(iter(jax.tree.leaves(params))).shape[0]
    first = sid == 0
    last = sid == S - 1

    buf0 = jnp.zeros_like(microbatches[0])
    outs0 = jnp.zeros_like(microbatches)

    def tick(t, carry):
        buf, outs = carry
        # stage 0 feeds micro-batch t; the others consume the activation
        # that rotated in from the previous stage last tick
        x = jnp.where(first, microbatches[jnp.clip(t, 0, M - 1)], buf)
        y = x
        for g in range(group):
            y = stage_fn(jax.tree.map(lambda a: a[g], params), y)
        # the last stage finished micro-batch t-(S-1) this tick
        w = t - (S - 1)
        valid = jnp.logical_and(last, jnp.logical_and(w >= 0, w < M))
        wc = jnp.clip(w, 0, M - 1)
        outs = outs.at[wc].set(jnp.where(valid, y, outs[wc]))
        # rotate activations one hop along the ring (stage s -> s+1)
        buf_next = jax.lax.ppermute(
            y, axis, [(i, (i + 1) % S) for i in range(S)])
        return buf_next, outs

    _, outs = jax.lax.fori_loop(0, M + S - 1, tick, (buf0, outs0))
    # only the last stage holds real outputs; masked psum replicates them
    outs = jax.lax.psum(jnp.where(last, outs, 0.0), axis)
    return outs


def spmd_pipeline(stage_fn: Callable, stacked_params, microbatches, mesh,
                  axis: str = "pp", batch_axes=(), remat=None):
    """Run the compiled pipeline.

    stage_fn(params_one_stage, x) -> y with y.shape == x.shape.
    stacked_params: pytree of [L, ...] arrays (see stack_layer_params); L
    must be a multiple of the pp axis size — each stage runs L/S
    consecutive layers per tick.
    microbatches: [M, B, ...] array; M micro-batches of the global batch.
    batch_axes: mesh axes sharding the batch dim (dp composition).
    remat: None | "dots" | "full" | jax checkpoint policy — see
    remat_policy. Returns [M, B, ...] outputs of the final stage.
    """
    jmesh = mesh.to_jax_mesh() if hasattr(mesh, "to_jax_mesh") else mesh
    n_stages = dict(zip(jmesh.axis_names, jmesh.devices.shape))[axis]
    n_layers = next(iter(jax.tree.leaves(stacked_params))).shape[0]
    if n_layers % n_stages != 0:
        raise ValueError(
            f"stacked layer count {n_layers} must be a multiple of the "
            f"'{axis}' axis size {n_stages}")
    stage_fn = _maybe_remat(stage_fn, remat)
    ndim = microbatches.ndim
    data_spec = P(None, tuple(batch_axes) or None,
                  *([None] * (ndim - 2)))
    param_specs = jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params)
    fn = shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn, axis=axis),
        mesh=jmesh, in_specs=(param_specs, data_spec),
        out_specs=data_spec, check_vma=False)
    return fn(stacked_params, microbatches)


def _pipeline_interleaved_local(params, microbatches, *, stage_fn, axis,
                                num_chunks):
    """Circular interleaved schedule inside shard_map.

    params: [V, 1(stage), ...] — this stage's V chunk slices, each chunk
    possibly holding several consecutive layers ([V, 1, G, ...]).
    Each in-flight activation carries (value, chunk v, micro-batch m,
    alive); it laps the ring V times, one chunk per lap, and dies after
    chunk V-1 on the last stage. Stage 0 injects a new micro-batch
    whenever its slot arrives dead. Per tick each stage runs ONE chunk
    (vs the non-interleaved schedule's V consecutive layers), so the
    fill/drain bubble shrinks by the factor V — the compiled analog of
    the reference's VPP (pipeline_parallel.py:1174
    PipelineParallelWithInterleave).
    """
    S = jax.lax.psum(1, axis)
    sid = jax.lax.axis_index(axis)
    V = num_chunks
    M = microbatches.shape[0]
    first = sid == 0
    last = sid == S - 1
    # local param layout: [V, 1 (this stage's slice), G, ...]
    group = next(iter(jax.tree.leaves(params))).shape[2]

    def run_chunk(v, x):
        def chunk_branch(vv):
            def br(xx):
                y = xx
                for g in range(group):
                    y = stage_fn(
                        jax.tree.map(lambda a: a[vv, 0, g], params), y)
                return y
            return br
        return jax.lax.switch(v, [chunk_branch(vv) for vv in range(V)], x)

    buf0 = jnp.zeros_like(microbatches[0])
    outs0 = jnp.zeros_like(microbatches)

    def tick(t, carry):
        buf, v, m, alive, next_m, outs = carry
        # stage 0: inject a fresh micro-batch into a dead slot
        inject = jnp.logical_and(first,
                                 jnp.logical_and(~alive, next_m < M))
        x = jnp.where(inject, microbatches[jnp.clip(next_m, 0, M - 1)],
                      buf)
        v = jnp.where(inject, 0, v)
        m = jnp.where(inject, next_m, m)
        alive = jnp.logical_or(alive, inject)
        next_m = next_m + inject.astype(jnp.int32)

        y = jnp.where(alive, run_chunk(jnp.clip(v, 0, V - 1), x), x)

        # the last stage on the final lap completes micro-batch m
        done = jnp.logical_and(alive, jnp.logical_and(last, v == V - 1))
        wc = jnp.clip(m, 0, M - 1)
        outs = outs.at[wc].set(jnp.where(done, y, outs[wc]))

        # lap counter bumps on the wrap from stage S-1 to stage 0
        v_next = v + jnp.where(last, 1, 0)
        alive_next = jnp.logical_and(alive, ~done)
        perm = [(i, (i + 1) % S) for i in range(S)]
        buf_n = jax.lax.ppermute(y, axis, perm)
        v_n = jax.lax.ppermute(v_next, axis, perm)
        m_n = jax.lax.ppermute(m, axis, perm)
        alive_n = jax.lax.ppermute(alive_next, axis, perm)
        return buf_n, v_n, m_n, alive_n, next_m, outs

    waves = (M + S - 1) // S
    T = waves * V * S + S
    _, _, _, _, _, outs = jax.lax.fori_loop(
        0, T, tick,
        (buf0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
         jnp.zeros((), jnp.bool_), jnp.zeros((), jnp.int32), outs0))
    outs = jax.lax.psum(jnp.where(last, outs, 0.0), axis)
    return outs


def spmd_pipeline_interleaved(stage_fn: Callable, stacked_params,
                              microbatches, mesh, axis: str = "pp",
                              batch_axes=(), num_chunks: int = 2,
                              remat=None):
    """Interleaved (virtual-pipeline) compiled schedule.

    Layer l of the [L, ...] stack runs as chunk l // (L/V/S') ... —
    concretely the stack is reshaped to [V, S, G, ...] so stage s owns
    chunks {v: layers (v*S + s)*G .. +G}, the round-robin placement of
    the reference's VPP (pp_layers.py get_stage_from_index for
    interleave). L must be divisible by V*S. The reference's zero-bubble
    variants exist to fill the dx/dW host schedule; under whole-program
    compilation XLA schedules those kernels inside one executable, so the
    compiled pipeline already has no host-induced bubble.
    """
    jmesh = mesh.to_jax_mesh() if hasattr(mesh, "to_jax_mesh") else mesh
    S = dict(zip(jmesh.axis_names, jmesh.devices.shape))[axis]
    L = next(iter(jax.tree.leaves(stacked_params))).shape[0]
    V = num_chunks
    if L % (V * S) != 0:
        raise ValueError(
            f"layer count {L} must be a multiple of num_chunks*stages "
            f"= {V}*{S}")
    G = L // (V * S)
    stage_fn = _maybe_remat(stage_fn, remat)
    # [L, ...] -> [V, S, G, ...]: layer (v*S + s)*G + g -> [v, s, g]
    params_vsg = jax.tree.map(
        lambda a: a.reshape((V, S, G) + a.shape[1:]), stacked_params)
    ndim = microbatches.ndim
    data_spec = P(None, tuple(batch_axes) or None,
                  *([None] * (ndim - 2)))
    param_specs = jax.tree.map(
        lambda a: P(None, axis, *([None] * (a.ndim - 2))), params_vsg)
    fn = shard_map(
        functools.partial(_pipeline_interleaved_local, stage_fn=stage_fn,
                          axis=axis, num_chunks=V),
        mesh=jmesh, in_specs=(param_specs, data_spec),
        out_specs=data_spec, check_vma=False)
    return fn(params_vsg, microbatches)
