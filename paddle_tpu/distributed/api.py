"""Semi-auto-parallel DTensor API: shard_tensor / reshard / shard_layer.

ref: python/paddle/distributed/auto_parallel/api.py:727 (reshard),
paddle/phi/core/distributed/auto_parallel/dist_tensor.h:39 (DistTensor =
local shard + TensorDistAttr{mesh, placements}). TPU-native mapping: the
"DistTensor" is simply a Tensor whose jax.Array carries a NamedSharding
(GSPMD); the reference's pairwise reshard-function lattice
(ref: auto_parallel/reshard/*_reshard_function.cc) collapses to
jax.device_put with a new sharding — XLA inserts the all-gather /
slice / all-to-all — except Partial, which we materialize with a psum
via shard_map before re-placing.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from .placement import Partial, Placement, Replicate, Shard
from .process_mesh import ProcessMesh

__all__ = [
    "DistAttr", "shard_tensor", "dtensor_from_fn", "reshard", "shard_layer",
    "unshard_dtensor", "placements_to_spec", "shard_parameter",
    "shard_batch",
]


class DistAttr:
    """TensorDistAttr analog (ref: dist_tensor.h:39): mesh + placements."""

    def __init__(self, mesh: ProcessMesh, placements: Sequence[Placement]):
        self.process_mesh = mesh
        self.placements = list(placements)

    def __repr__(self):
        return f"DistAttr(mesh={self.process_mesh.shape}, placements={self.placements})"


def placements_to_spec(mesh: ProcessMesh, placements: Sequence[Placement],
                       ndim: int) -> P:
    """[Shard(0), Replicate()] on mesh axes -> PartitionSpec per tensor dim.

    Mirrors dims_mapping in the reference (ref: process_mesh + dims_mapping in
    phi/core/distributed/auto_parallel/dist_attr.h): mesh axis i shards tensor
    dim placements[i].dim. Multiple mesh axes on one tensor dim stack into a
    tuple spec entry (the GSPMD composite-axes form).
    """
    dim_axes: List[Optional[object]] = [None] * ndim
    for axis_name, placement in zip(mesh.dim_names, placements):
        if isinstance(placement, Shard):
            d = placement.dim % ndim
            if dim_axes[d] is None:
                dim_axes[d] = axis_name
            elif isinstance(dim_axes[d], tuple):
                dim_axes[d] = dim_axes[d] + (axis_name,)
            else:
                dim_axes[d] = (dim_axes[d], axis_name)
    return P(*dim_axes)


def _named_sharding(mesh: ProcessMesh, placements: Sequence[Placement],
                    ndim: int) -> NamedSharding:
    return NamedSharding(mesh.to_jax_mesh(),
                         placements_to_spec(mesh, placements, ndim))


def _normalize_placements(mesh: ProcessMesh,
                          placements: Optional[Sequence[Placement]]):
    if placements is None:
        return [Replicate() for _ in range(mesh.ndim)]
    placements = list(placements)
    while len(placements) < mesh.ndim:
        placements.append(Replicate())
    return placements


def shard_tensor(data, mesh: ProcessMesh,
                 placements: Optional[Sequence[Placement]] = None,
                 dtype=None, stop_gradient=None) -> Tensor:
    """ref: python/paddle/distributed/auto_parallel/api.py shard_tensor."""
    from ..core.tensor import to_tensor
    t = data if isinstance(data, Tensor) else to_tensor(data, dtype=dtype)
    placements = _normalize_placements(mesh, placements)
    sharding = _named_sharding(mesh, placements, t._data.ndim)
    arr = jax.device_put(t._data, sharding)
    sg = t.stop_gradient if stop_gradient is None else stop_gradient
    out = Tensor(arr, stop_gradient=sg)
    out._dist_attr = DistAttr(mesh, placements)
    if isinstance(data, Tensor):
        out.name = data.name
    return out


def shard_batch(data, mesh: ProcessMesh,
                placements: Optional[Sequence[Placement]] = None,
                dtype=None) -> Tensor:
    """Assemble each process's LOCAL batch shard into one global
    DistTensor — the multi-controller data-feeding contract: every rank's
    DataLoader yields only ITS OWN rows (the reference's
    DistributedBatchSampler split, ref: python/paddle/io/dataloader —
    each NCCL rank feeds its local batch), and the global array spanning
    the mesh is assembled from those per-process pieces without any rank
    ever holding the full batch.

    Default placement shards dim 0 along the mesh's FIRST axis. On a
    single controller this degenerates to shard_tensor (local == global).
    """
    import numpy as np
    placements = _normalize_placements(
        mesh, placements if placements is not None else [Shard(0)])
    local = data._data if isinstance(data, Tensor) else data
    local = np.asarray(local, dtype=dtype)
    sharding = _named_sharding(mesh, placements, local.ndim)
    if jax.process_count() == 1:
        arr = jax.device_put(local, sharding)
    else:
        arr = jax.make_array_from_process_local_data(sharding, local)
    out = Tensor(arr, stop_gradient=True)
    out._dist_attr = DistAttr(mesh, placements)
    return out


def dtensor_from_fn(fn, mesh: ProcessMesh,
                    placements: Sequence[Placement], *args, **kwargs) -> Tensor:
    """ref: auto_parallel/api.py dtensor_from_fn."""
    return shard_tensor(fn(*args, **kwargs), mesh, placements)


def _materialize_partial(t: Tensor, mesh: ProcessMesh,
                         placements: List[Placement]) -> Tensor:
    """psum away Partial placements so only Shard/Replicate remain."""
    from jax import shard_map

    partial_axes = [mesh.dim_names[i] for i, p in enumerate(placements)
                    if isinstance(p, Partial)]
    if not partial_axes:
        return t
    jmesh = mesh.to_jax_mesh()
    in_spec = placements_to_spec(mesh, placements, t._data.ndim)

    def _reduce(x):
        return jax.lax.psum(x, tuple(partial_axes))

    fn = shard_map(_reduce, mesh=jmesh, in_specs=(in_spec,), out_specs=in_spec)
    arr = jax.jit(fn)(t._data)
    new_placements = [Replicate() if isinstance(p, Partial) else p
                      for p in placements]
    out = Tensor(arr, stop_gradient=t.stop_gradient)
    out._dist_attr = DistAttr(mesh, new_placements)
    return out


def reshard(t: Tensor, mesh: ProcessMesh,
            placements: Sequence[Placement]) -> Tensor:
    """ref: auto_parallel/api.py:727. All lattice transitions (r<->s, s<->s
    alltoall, p->r, p->s, cross-mesh) reduce to: psum partials, then
    device_put with the target NamedSharding (XLA emits the collective)."""
    placements = _normalize_placements(mesh, placements)
    src_attr = getattr(t, "_dist_attr", None)
    if src_attr is not None and any(isinstance(p, Partial)
                                    for p in src_attr.placements):
        t = _materialize_partial(t, src_attr.process_mesh, src_attr.placements)
    if any(isinstance(p, Partial) for p in placements):
        raise ValueError("reshard target placements cannot be Partial")
    sharding = _named_sharding(mesh, placements, t._data.ndim)
    arr = jax.device_put(t._data, sharding)
    out = Tensor(arr, stop_gradient=t.stop_gradient)
    out._dist_attr = DistAttr(mesh, list(placements))
    return out


def shard_layer(layer, process_mesh: ProcessMesh,
                shard_fn=None, input_fn=None, output_fn=None):
    """ref: auto_parallel/api.py shard_layer — apply shard_fn(name, layer,
    mesh) to every sublayer to re-place its params; default replicates."""
    def _default_shard_fn(name, sublayer, mesh):
        for pname, param in list(sublayer._parameters.items()):
            if param is not None:
                sharded = shard_tensor(
                    param, mesh, [Replicate() for _ in range(mesh.ndim)])
                param._data = sharded._data
                param._dist_attr = sharded._dist_attr

    fn = shard_fn or _default_shard_fn
    for name, sublayer in layer.named_sublayers(include_self=True):
        fn(name, sublayer, process_mesh)
    if input_fn is not None:
        layer.register_forward_pre_hook(
            lambda _layer, inputs: input_fn(inputs, process_mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(
            lambda _layer, inputs, outputs: output_fn(outputs, process_mesh))
    return layer


def shard_parameter(param, mesh: ProcessMesh, tp_axis: Optional[str] = None,
                    fsdp_axis: Optional[str] = None,
                    tp_dim: Optional[int] = None,
                    fsdp_dim: Optional[int] = None) -> None:
    """In-place tp/fsdp placement for one parameter — the shared placement
    algebra behind the model zoo's shard_* rule tables (ref: the per-weight
    shard_tensor calls in semi_auto_parallel_llama_model.py).

    tp_dim shards on tp_axis (column=out dim, row=in dim for [in, out]
    weights); fsdp_dim shards the remaining dim on fsdp_axis unless it
    would collide with the tp split. Dims beyond the param's rank are
    ignored.
    """
    axis_names = list(mesh.dim_names)
    placements: List[Placement] = [Replicate() for _ in axis_names]
    ndim = param._data.ndim
    if tp_axis in axis_names and tp_dim is not None and tp_dim < ndim:
        placements[axis_names.index(tp_axis)] = Shard(tp_dim)
    else:
        tp_dim = None
    if (fsdp_axis in axis_names and fsdp_dim is not None
            and fsdp_dim < ndim and fsdp_dim != tp_dim):
        placements[axis_names.index(fsdp_axis)] = Shard(fsdp_dim)
    sharded = shard_tensor(param, mesh, placements,
                           stop_gradient=param.stop_gradient)
    param._data = sharded._data
    param._dist_attr = sharded._dist_attr


def unshard_dtensor(t: Tensor) -> Tensor:
    """Gather a DistTensor to a fully-replicated dense tensor.

    ref: auto_parallel/api.py unshard_dtensor."""
    attr = getattr(t, "_dist_attr", None)
    if attr is None:
        return t
    if any(isinstance(p, Partial) for p in attr.placements):
        t = _materialize_partial(t, attr.process_mesh, attr.placements)
        attr = t._dist_attr
    mesh = attr.process_mesh
    sharding = _named_sharding(
        mesh, [Replicate()] * mesh.ndim, t._data.ndim)
    out = Tensor(jax.device_put(t._data, sharding),
                 stop_gradient=t.stop_gradient)
    out._dist_attr = None
    return out
