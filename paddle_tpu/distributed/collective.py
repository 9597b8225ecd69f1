"""Eager collective communication API + Group bookkeeping.

ref: python/paddle/distributed/communication/ (all_reduce.py etc.) and
paddle/fluid/distributed/collective/process_group_nccl.cc. TPU-native design
(SURVEY.md §5 "Distributed communication backend"): instead of NCCL comms on
a side stream, each collective is a tiny cached XLA executable over the
group's device mesh — the collective rides ICI inside the compiled program.

Three operating regimes:
- single-controller (default, incl. tests with 8 virtual CPU devices): one
  Python process drives all chips; "ranks" are devices. Eager collectives on
  replicated host values are identity-like (world through jit is the real
  path); collectives on device-sharded DistTensors run compiled psum etc.
- multi-process with a global jax runtime (jax.distributed.initialize):
  compiled one-collective XLA executables span hosts (ICI/DCN).
- multi-process without a global jax runtime (launch CLI on CPU, or eager
  p2p/object exchange): a TCPStore channel transport
  (ref: process_group_nccl.cc:834 + store/tcp_store.h:121 — the reference
  likewise bootstraps every comm ring through its store). Tensors are
  host-staged through the store; this is the correctness path — the
  bandwidth path is always the compiled collective inside jit.
"""
from __future__ import annotations

import functools
import os
import pickle
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.flags import define_flag
from ..core.tensor import Tensor

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "all_reduce", "all_gather",
    "all_gather_object", "broadcast", "broadcast_object_list", "reduce",
    "scatter", "scatter_object_list", "alltoall", "alltoall_single", "send",
    "recv", "isend", "irecv", "barrier", "reduce_scatter", "stream",
    "P2POp", "batch_isend_irecv", "get_backend", "destroy_process_group",
    "is_available", "bucket_assignment", "bucketed_grad_sync",
]

define_flag(
    "dist_grad_bucket_bytes", 4 << 20,
    "Gradient-bucket byte target for the captured distributed train "
    "step (DistTrainStep): grads group into buckets of ~this many "
    "bytes in reverse-backward order and each bucket's all-reduce/"
    "reduce-scatter is emitted as its own first-class node in the "
    "captured program (an optimization_barrier chain pins bucket "
    "order), so XLA's async collectives overlap gradient sync with "
    "remaining backward compute instead of running one serial "
    "epilogue. 0 disables bucketing (pre-T3 program shape: sharding "
    "propagation places the collectives)")


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Task:
    """Async collective handle (ref: process_group.h Task). XLA dispatch is
    already async; wait() blocks on the result buffer."""

    def __init__(self, arrays):
        self._arrays = arrays

    def wait(self):
        for a in self._arrays:
            if hasattr(a, "block_until_ready"):
                a.block_until_ready()

    def is_completed(self):
        return True


class Group:
    """ref: python/paddle/distributed/communication/group.py Group."""

    def __init__(self, gid: int, ranks: List[int]):
        self.id = gid
        self.ranks = list(ranks)
        self.nranks = len(ranks)
        # per-group collective sequence numbers (all members call group
        # collectives in the same order, so local counters agree — the same
        # invariant NCCL imposes on its rings)
        self._seq: Dict[str, int] = {}

    @property
    def world_size(self):
        return self.nranks

    @property
    def rank(self):
        """This process's rank within the group (-1 if not a member)."""
        grank = _global_rank()
        return self.ranks.index(grank) if grank in self.ranks else -1

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    @property
    def process_group(self):
        return self

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks})"


_group_map = {}
_group_counter = 0


def _global_rank() -> int:
    """Env-aware: launched CPU workers have jax.process_count()==1 but a
    real rank from the launcher (PADDLE_TRAINER_ID)."""
    if jax.process_count() > 1:
        return jax.process_index()
    return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def _world_size() -> int:
    if jax.process_count() > 1:
        return jax.process_count()
    return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))


def _ensure_default_group() -> Group:
    if 0 not in _group_map:
        _group_map[0] = Group(0, list(range(max(_world_size(), 1))))
    return _group_map[0]


def get_group(gid: int = 0) -> Group:
    if gid == 0:
        return _ensure_default_group()
    return _group_map[gid]


def _get_group(group: Optional[Group]) -> Group:
    return group if group is not None else _ensure_default_group()


def new_group(ranks: Optional[List[int]] = None, backend=None, timeout=None) -> Group:
    """ref: communication/group.py new_group."""
    global _group_counter
    _group_counter += 1
    if ranks is None:
        ranks = list(range(max(_world_size(), 1)))
    g = Group(_group_counter, sorted(ranks))
    _group_map[g.id] = g
    return g


def _unwrap(t):
    return t._data if isinstance(t, Tensor) else jnp.asarray(t)


def _mode(g: Group) -> str:
    """Pick the execution regime for a collective on group ``g``."""
    if g.nranks <= 1:
        return "local"
    if jax.process_count() > 1:
        return "compiled"
    if _world_size() > 1:
        return "store"
    return "local"


# -- TCPStore channel transport ----------------------------------------------
# Host-staged tensor/object exchange for eager p2p and for collectives in
# launched multi-process jobs that don't bring up a global jax runtime.
# ref: the reference's ProcessGroup bootstraps every ring through its store
# (process_group_nccl.cc CreateNCCLEnvCache); here the store IS the eager
# transport — the fast path is always the compiled collective inside jit.

_store = None


def _comm_store():
    global _store
    if _store is None:
        from .store import TCPStore
        master = os.environ.get("PADDLE_MASTER",
                                os.environ.get("MASTER_ADDR", ""))
        if not master:
            raise RuntimeError(
                "cross-process eager collectives need PADDLE_MASTER "
                "(set by paddle_tpu.distributed.launch)")
        if ":" in master:
            host, port = master.rsplit(":", 1)
            port = int(port)
        else:
            host, port = master, int(os.environ.get("MASTER_PORT", "29500"))
        # comm store lives next to the coordinator port
        _store = TCPStore(host, port + 1, is_master=_global_rank() == 0,
                          world_size=_world_size(),
                          timeout=float(os.environ.get(
                              "PADDLE_STORE_TIMEOUT", "120")))
    return _store


def _store_available() -> bool:
    return _store is not None or bool(
        os.environ.get("PADDLE_MASTER", os.environ.get("MASTER_ADDR", "")))


def _allgather_bytes(g: Group, payload: bytes, tag: str) -> List[bytes]:
    """Gather one bytes payload per rank. Uses the TCPStore when the
    launcher env provides one; in a compiled multi-process regime without
    a store (e.g. TPU auto-bootstrap), falls back to a size-exchange +
    padded uint8 compiled all_gather."""
    if _store_available():
        st = _comm_store()
        base = f"c{g.id}/{tag}/{_next_seq(g, tag)}"
        st.set(f"{base}/{g.rank}", payload)
        parts = [st.get(f"{base}/{i}") for i in range(g.nranks)]
        if st.add(f"{base}/rc", 1) == g.nranks:
            for i in range(g.nranks):
                st.delete(f"{base}/{i}")
            st.delete(f"{base}/rc")
        return parts
    buf = np.frombuffer(payload, dtype=np.uint8)
    sizes = _cross_process(
        "all_gather", jnp.asarray(np.array([buf.size], np.int32)),
        g).reshape(g.nranks)
    maxlen = int(sizes.max())
    padded = np.zeros(maxlen, np.uint8)
    padded[:buf.size] = buf
    gathered = _cross_process("all_gather", jnp.asarray(padded), g)
    return [gathered[i][:sizes[i]].tobytes() for i in range(g.nranks)]


def _pack(arr) -> bytes:
    return pickle.dumps(np.asarray(arr), protocol=4)


def _unpack(b: bytes):
    return jnp.asarray(pickle.loads(b))


def _next_seq(g: Group, tag: str) -> int:
    n = g._seq.get(tag, 0)
    g._seq[tag] = n + 1
    return n


def _reduce_parts(parts, op, nranks):
    out = parts[0]
    for p in parts[1:]:
        if op in (ReduceOp.SUM, ReduceOp.AVG):
            out = out + p
        elif op == ReduceOp.MAX:
            out = np.maximum(out, p)
        elif op == ReduceOp.MIN:
            out = np.minimum(out, p)
        elif op == ReduceOp.PROD:
            out = out * p
        else:
            raise NotImplementedError(op)
    if op == ReduceOp.AVG:
        out = out / nranks
    return out


def _store_gather_all(g: Group, arr, tag: str):
    """Every member contributes its array; every member reads all parts
    (host numpy). Shares the set/read-all/refcounted-delete protocol with
    _allgather_bytes."""
    return [pickle.loads(p) for p in _allgather_bytes(g, _pack(arr), tag)]


def _store_bcast_bytes(g: Group, payload: Optional[bytes], src_rank: int,
                       tag: str) -> bytes:
    st = _comm_store()
    base = f"c{g.id}/{tag}/{_next_seq(g, tag)}"
    if g.rank == src_rank:
        st.set(base, payload)
        out = payload
    else:
        out = st.get(base)
    if st.add(f"{base}/rc", 1) == g.nranks:
        st.delete(base)
        st.delete(f"{base}/rc")
    return out


def _store_barrier(g: Group):
    st = _comm_store()
    base = f"c{g.id}/bar/{_next_seq(g, 'bar')}"
    if st.add(f"{base}/cnt", 1) == g.nranks:
        st.set(f"{base}/done", b"1")
    st.wait(f"{base}/done")
    if st.add(f"{base}/rc", 1) == g.nranks:
        st.delete(f"{base}/cnt")
        st.delete(f"{base}/done")
        st.delete(f"{base}/rc")


# Single-process emulation mailbox for send/recv, keyed by
# (group_id, src, dst) so interleaved channels can't cross wires
# (each directed edge is its own FIFO).
_mailbox: Dict[Tuple[int, int, int], List] = {}


# -- multi-process compiled collectives --------------------------------------
# The production (regime-2) transport: a one-collective XLA program over a
# mesh of one device per participating process — psum/all_gather ride the
# interconnect (ICI/DCN on TPU pods, gloo on the CPU test backend) inside
# the compiled program, exactly like the reference's per-ring NCCL comm
# (ref: process_group_nccl.cc:732 CreateNCCLEnvCache per place). Every
# group member must call in (same SPMD contract as NCCL).

@functools.lru_cache(maxsize=None)
def _rank_device(rank: int):
    """The device owned by global rank ``rank`` (multi-controller: one
    process per rank, first local device of that process)."""
    for d in jax.devices():
        if d.process_index == rank:
            return d
    raise RuntimeError(
        f"no device owned by process {rank}; "
        f"process_count={jax.process_count()}")


@functools.lru_cache(maxsize=None)
def _group_mesh(ranks: tuple):
    from jax.sharding import Mesh
    devs = np.asarray([_rank_device(r) for r in ranks], dtype=object)
    return Mesh(devs, axis_names=("r",))


def _cross_process(op_name, arr, group: Group, **kw):
    """Run a one-collective compiled program over the group's ranks and
    return this rank's result as a host numpy array
    (all_reduce -> arr.shape, all_gather -> (nranks,) + arr.shape)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    mesh = _group_mesh(tuple(group.ranks))
    arr = jnp.asarray(arr)
    x = jax.make_array_from_single_device_arrays(
        (group.nranks,) + arr.shape,
        NamedSharding(mesh, P("r")),
        [jax.device_put(arr[None], jax.local_devices()[0])])

    if op_name == "all_reduce":
        red = kw.get("op", ReduceOp.SUM)
        def f(v):
            v = v[0]
            if red in (ReduceOp.SUM, ReduceOp.AVG):
                out = jax.lax.psum(v, "r")
                if red == ReduceOp.AVG:
                    out = out / group.nranks
            elif red == ReduceOp.MAX:
                out = jax.lax.pmax(v, "r")
            elif red == ReduceOp.MIN:
                out = jax.lax.pmin(v, "r")
            else:
                raise NotImplementedError(red)
            return out[None]
    elif op_name == "all_gather":
        def f(v):
            return jax.lax.all_gather(v[0], "r")
    else:
        raise NotImplementedError(op_name)

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("r"),),
                           out_specs=P("r")))
    out = fn(x)
    # this rank's shard IS its result; a global np.asarray would need
    # non-addressable remote shards and fail in multi-controller mode
    local = np.asarray(out.addressable_shards[0].data)
    return local[0] if op_name == "all_reduce" else local


# -- public API ---------------------------------------------------------------

def all_reduce(tensor, op=ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True) -> Task:
    """ref: communication/all_reduce.py:29. In-place on `tensor`."""
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        # single-controller: value already holds the full contribution
        if op == ReduceOp.AVG and g.nranks > 1:
            tensor._data = _unwrap(tensor) / g.nranks
        return Task([_unwrap(tensor)])
    if m == "store":
        parts = _store_gather_all(g, _unwrap(tensor), "ar")
        tensor._data = jnp.asarray(_reduce_parts(parts, op, g.nranks))
        return Task([tensor._data])
    out = _cross_process("all_reduce", _unwrap(tensor), g, op=op)
    tensor._data = jnp.asarray(out)
    return Task([tensor._data])


def all_gather(tensor_list: List, tensor, group: Optional[Group] = None,
               sync_op: bool = True) -> Task:
    """ref: communication/all_gather.py."""
    g = _get_group(group)
    arr = _unwrap(tensor)
    m = _mode(g)
    if m == "local":
        for _ in range(g.nranks):
            tensor_list.append(Tensor(jnp.asarray(arr)))
        return Task([arr])
    if m == "store":
        parts = _store_gather_all(g, arr, "ag")
        tensor_list.extend(Tensor(jnp.asarray(p)) for p in parts)
        return Task([arr])
    host = _cross_process("all_gather", arr, g)
    for i in range(g.nranks):
        tensor_list.append(Tensor(jnp.asarray(host[i])))
    return Task([arr])


def all_gather_object(object_list: List, obj, group: Optional[Group] = None):
    g = _get_group(group)
    if _mode(g) == "local":
        object_list.extend(obj for _ in range(g.nranks))
        return
    parts = _allgather_bytes(g, pickle.dumps(obj, protocol=4), "ago")
    object_list.extend(pickle.loads(p) for p in parts)


def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True) -> Task:
    """ref: communication/broadcast.py. Single-controller: identity."""
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        return Task([_unwrap(tensor)])
    if m == "store":
        sr = g.get_group_rank(src)
        payload = _pack(_unwrap(tensor)) if g.rank == sr else None
        out = _store_bcast_bytes(g, payload, sr, "bc")
        if g.rank != sr:
            tensor._data = _unpack(out)
        return Task([_unwrap(tensor)])
    # compiled regime: psum of (value if rank==src else zeros). Costs one
    # allreduce (~2x a tree broadcast's bytes) but stays on ICI and fuses
    # under jit; the store path above is the host-staged alternative.
    arr = _unwrap(tensor)
    if g.rank != g.get_group_rank(src):
        arr = jnp.zeros_like(arr)
    t = Tensor(arr)
    task = all_reduce(t, ReduceOp.SUM, g)
    tensor._data = t._data
    return task


def broadcast_object_list(object_list: List, src: int = 0,
                          group: Optional[Group] = None):
    """ref: communication/broadcast.py broadcast_object_list — in-place."""
    g = _get_group(group)
    if _mode(g) == "local":
        return
    sr = g.get_group_rank(src)
    if _store_available():
        payload = (pickle.dumps(list(object_list), protocol=4)
                   if g.rank == sr else None)
        out = _store_bcast_bytes(g, payload, sr, "bco")
    else:  # compiled regime without a store: gather, keep src's payload
        mine = pickle.dumps(list(object_list) if g.rank == sr else None,
                            protocol=4)
        out = _allgather_bytes(g, mine, "bco")[sr]
    if g.rank != sr:
        object_list[:] = pickle.loads(out)


def reduce(tensor, dst: int = 0, op=ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True) -> Task:
    """ref: communication/reduce.py — only ``dst`` holds the reduced value
    afterwards; other ranks' tensors are left untouched."""
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        return all_reduce(tensor, op, group)
    if m == "store":
        st = _comm_store()
        dr = g.get_group_rank(dst)
        base = f"c{g.id}/rd/{_next_seq(g, 'rd')}"
        if g.rank == dr:
            parts = [np.asarray(_unwrap(tensor))]
            parts += [pickle.loads(st.take(f"{base}/{i}"))
                      for i in range(g.nranks) if i != dr]
            tensor._data = jnp.asarray(_reduce_parts(parts, op, g.nranks))
        else:
            st.set(f"{base}/{g.rank}", _pack(_unwrap(tensor)))
        return Task([_unwrap(tensor)])
    # compiled regime: allreduce, then non-dst ranks restore their input
    # (dst-selectivity is semantic, not a bandwidth saving, on a ring)
    orig = _unwrap(tensor)
    task = all_reduce(tensor, op, group)
    if g.rank != g.get_group_rank(dst):
        tensor._data = orig
    return task


def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True) -> Task:
    """ref: communication/scatter.py — ``src`` distributes tensor_list[i]
    to group rank i."""
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        if tensor_list:
            tensor._data = _unwrap(tensor_list[0])
        return Task([_unwrap(tensor)])
    st = _comm_store()
    sr = g.get_group_rank(src)
    base = f"c{g.id}/sc/{_next_seq(g, 'sc')}"
    if g.rank == sr:
        if not tensor_list or len(tensor_list) != g.nranks:
            raise ValueError(
                f"scatter src needs tensor_list of len {g.nranks}")
        for i in range(g.nranks):
            if i == sr:
                tensor._data = _unwrap(tensor_list[i])
            else:
                st.set(f"{base}/{i}", _pack(_unwrap(tensor_list[i])))
    else:
        tensor._data = _unpack(st.take(f"{base}/{g.rank}"))
    return Task([_unwrap(tensor)])


def scatter_object_list(out_object_list: List, in_object_list=None,
                        src: int = 0, group: Optional[Group] = None):
    """ref: communication/scatter.py scatter_object_list."""
    g = _get_group(group)
    if _mode(g) == "local":
        if in_object_list:
            out_object_list[:] = [in_object_list[0]]
        return
    sr = g.get_group_rank(src)
    if g.rank == sr and (in_object_list is None or
                         len(in_object_list) != g.nranks):
        raise ValueError(
            f"scatter src needs in_object_list of len {g.nranks}")
    if _store_available():
        st = _comm_store()
        base = f"c{g.id}/sco/{_next_seq(g, 'sco')}"
        if g.rank == sr:
            for i in range(g.nranks):
                if i != sr:
                    st.set(f"{base}/{i}",
                           pickle.dumps(in_object_list[i], protocol=4))
            out_object_list[:] = [in_object_list[sr]]
        else:
            out_object_list[:] = [pickle.loads(st.take(f"{base}/{g.rank}"))]
    else:  # compiled regime without a store: gather src's list, pick own
        mine = pickle.dumps(in_object_list if g.rank == sr else None,
                            protocol=4)
        full = pickle.loads(_allgather_bytes(g, mine, "sco")[sr])
        out_object_list[:] = [full[g.rank]]


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op: bool = True) -> Task:
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        idx = max(g.rank, 0)
        t = Tensor(_unwrap(tensor_list[idx]))
        all_reduce(t, op, g)
        tensor._data = t._data
        return Task([tensor._data])
    stacked = jnp.stack([_unwrap(t) for t in tensor_list])
    if m == "store":
        parts = _store_gather_all(g, stacked, "rs")
        summed = _reduce_parts(parts, op, g.nranks)
        tensor._data = jnp.asarray(summed[g.rank])
        return Task([tensor._data])
    summed = _cross_process("all_reduce", stacked, g, op=op)
    tensor._data = jnp.asarray(summed)[g.rank]
    return Task([tensor._data])


def alltoall(out_tensor_list: List, in_tensor_list: List,
             group: Optional[Group] = None, sync_op: bool = True) -> Task:
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        out_tensor_list.extend(Tensor(_unwrap(t)) for t in in_tensor_list)
        return Task([])
    if m == "store":
        st = _comm_store()
        base = f"c{g.id}/a2a/{_next_seq(g, 'a2a')}"
        r = g.rank
        for d in range(g.nranks):
            if d != r:
                st.set(f"{base}/{r}>{d}", _pack(_unwrap(in_tensor_list[d])))
        for s in range(g.nranks):
            if s == r:
                out_tensor_list.append(Tensor(_unwrap(in_tensor_list[r])))
            else:
                out_tensor_list.append(Tensor(_unpack(
                    st.take(f"{base}/{s}>{r}"))))
        return Task([])
    stacked = jnp.stack([_unwrap(t) for t in in_tensor_list])
    gathered = _cross_process("all_gather", stacked, g)
    r = g.rank
    for i in range(g.nranks):
        out_tensor_list.append(Tensor(jnp.asarray(gathered[i][r])))
    return Task([])


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group: Optional[Group] = None,
                    sync_op: bool = True) -> Task:
    """ref: communication/all_to_all.py alltoall_single — axis-0 splits of
    one tensor exchanged pairwise."""
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        out_tensor._data = _unwrap(in_tensor)
        return Task([out_tensor._data])
    arr = _unwrap(in_tensor)
    n = g.nranks
    if in_split_sizes is None:
        if arr.shape[0] % n:
            raise ValueError(
                f"alltoall_single dim0 {arr.shape[0]} not divisible by "
                f"group size {n}")
        in_split_sizes = [arr.shape[0] // n] * n
    offs = np.cumsum([0] + list(in_split_sizes))
    chunks = [arr[offs[i]:offs[i + 1]] for i in range(n)]
    ins, outs = [Tensor(c) for c in chunks], []
    alltoall(outs, ins, group=g, sync_op=sync_op)
    out_tensor._data = jnp.concatenate([_unwrap(t) for t in outs], axis=0)
    return Task([out_tensor._data])


def send(tensor, dst: int = 0, group: Optional[Group] = None,
         sync_op: bool = True) -> Task:
    """ref: communication/send.py + process_group_nccl.cc:252 Send. Cross-
    process transport is the TCPStore channel (host-staged); per-directed-
    edge FIFO sequence numbers pair each send with its recv."""
    g = _get_group(group)
    if _mode(g) == "local":
        key = (g.id, _global_rank(), dst)
        _mailbox.setdefault(key, []).append(jnp.asarray(_unwrap(tensor)))
        return Task([])
    st = _comm_store()
    me = _global_rank()  # dst/src are GLOBAL ranks (paddle contract)
    seq = _next_seq(g, f"p2p/{me}>{dst}")
    st.set(f"c{g.id}/p2p/{me}>{dst}/{seq}", _pack(_unwrap(tensor)))
    return Task([])


def recv(tensor, src: int = 0, group: Optional[Group] = None,
         sync_op: bool = True) -> Task:
    g = _get_group(group)
    if _mode(g) == "local":
        key = (g.id, src, _global_rank())
        q = _mailbox.get(key)
        if not q:
            raise RuntimeError(
                f"recv(src={src}) has no pending message on channel "
                f"{key} (single-process mode cannot block)")
        tensor._data = q.pop(0)
        return Task([])
    st = _comm_store()
    me = _global_rank()
    seq = _next_seq(g, f"p2p/{src}>{me}")
    tensor._data = _unpack(st.take(f"c{g.id}/p2p/{src}>{me}/{seq}"))
    return Task([tensor._data])


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group, sync_op=False)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group, sync_op=False)


class P2POp:
    """ref: communication/batch_isend_irecv.py P2POp."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv, send, recv):
            raise ValueError("P2POp op must be paddle.distributed.isend/irecv")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list: List[P2POp]) -> List[Task]:
    """ref: communication/batch_isend_irecv.py. Sends are issued before
    recvs so the host-staged transport cannot deadlock on ordering."""
    sends = [p for p in p2p_op_list if p.op in (isend, send)]
    recvs = [p for p in p2p_op_list if p.op in (irecv, recv)]
    tasks = [p.op(p.tensor, p.peer, p.group) for p in sends]
    tasks += [p.op(p.tensor, p.peer, p.group) for p in recvs]
    return tasks


def barrier(group: Optional[Group] = None):
    g = _get_group(group)
    m = _mode(g)
    if m == "local":
        return
    if m == "store":
        _store_barrier(g)
        return
    t = Tensor(jnp.zeros((1,), jnp.float32))
    all_reduce(t, ReduceOp.SUM, g).wait()


def get_backend(group: Optional[Group] = None) -> str:
    """ref: communication/group.py get_backend (NCCL/GLOO there)."""
    dev = jax.devices()[0].platform
    return "XCCL" if dev == "tpu" else "GLOO"


def is_available() -> bool:
    return True


def destroy_process_group(group: Optional[Group] = None):
    """ref: communication/group.py destroy_process_group."""
    global _store
    if group is None or group.id == 0:
        _group_map.clear()
        _mailbox.clear()
        if _store is not None:
            _store.shutdown()
            _store = None
    else:
        _group_map.pop(group.id, None)


# -- bucketed gradient synchronization (T3 compute–collective overlap) --------
# The captured distributed train step (dist_train.DistTrainStep over
# jit/sot.CapturedStep) syncs gradients through these instead of leaving
# ONE sharding-propagation-placed collective epilogue after the full
# backward: grads group into size-targeted buckets in REVERSE-backward
# order (the last layers' grads retire first while earlier layers are
# still differentiating), each bucket's reduce materializes at its own
# pinned program point (with_sharding_constraint to the parameter's
# placement — reduce-scatter under ZeRO/fsdp, all-reduce under pure dp),
# and an optimization_barrier chain keeps XLA from collapsing the
# buckets back into a tail. Bucket k's collective depends ONLY on its
# own grads, so the latency-hiding scheduler can launch it while the
# remaining backward computes — the DDP/T3 tracking-and-triggering
# structure as a first-class piece of the captured DAG.

def bucket_assignment(named_sizes, target_bytes: int):
    """Greedy in-order bucketing: ``named_sizes`` is [(key, nbytes)]
    ALREADY in reverse-backward order; returns a list of buckets (each
    a list of keys) such that every key lands in exactly one bucket,
    order is preserved, each bucket closes once it reaches
    ``target_bytes`` (a single grad larger than the target gets its
    own bucket). ``target_bytes <= 0`` puts everything in one bucket."""
    if target_bytes <= 0:
        return [[k for k, _ in named_sizes]] if named_sizes else []
    buckets: List[List[str]] = []
    cur: List[str] = []
    cur_bytes = 0
    for key, nbytes in named_sizes:
        if cur and cur_bytes + int(nbytes) > target_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(key)
        cur_bytes += int(nbytes)
    if cur:
        buckets.append(cur)
    return buckets


def bucketed_grad_sync(grads: Dict[str, Any], buckets, shardings):
    """Trace-time: emit each bucket's gradient synchronization as its
    own program node. ``grads`` maps key -> grad array (tracers under
    jit), ``buckets`` is bucket_assignment's output, ``shardings``
    maps key -> the parameter's NamedSharding (keys without one pass
    through un-constrained — single-device runs). Returns
    ``(synced_grads, plan)`` where plan is
    [{"bucket", "grads", "bytes", "keys"}] for telemetry."""
    from jax import lax

    synced = dict(grads)
    plan: List[Dict[str, Any]] = []
    token = None
    for i, bucket in enumerate(buckets):
        leaves = [synced[k] for k in bucket]
        if token is not None:
            # pin: this bucket's sync cannot be hoisted before the
            # previous bucket's (reverse-backward issue order, the
            # same in-order guarantee DDP buckets give NCCL)
            barred = lax.optimization_barrier(tuple(leaves) + (token,))
            leaves = list(barred[:-1])
        out = []
        nbytes = 0
        for k, g in zip(bucket, leaves):
            sh = shardings.get(k)
            if sh is not None:
                # materialize the REDUCED, placement-correct grad HERE:
                # the partitioner lands the bucket's collective at this
                # program point instead of wherever the epilogue sits
                g = lax.with_sharding_constraint(g, sh)
            out.append(g)
            nbytes += int(np.prod(g.shape)) * np.dtype(g.dtype).itemsize
        token = out[0]
        plan.append({"bucket": i, "grads": len(bucket), "bytes": nbytes,
                     "keys": list(bucket)})
        for k, g in zip(bucket, out):
            synced[k] = g
    return synced, plan


def journal_grad_buckets(plan, dur_us=None) -> None:
    """Host-side: land one flight-recorder ``collective`` event per
    bucket (payload bytes + grad count — the T3 overlap-efficiency
    numerator next to PR 8's eager-collective events) plus a
    ``dist_step`` summary carrying the step's host dispatch duration.
    Flight-gated: the off path pays one flag read."""
    if not plan or not _flight.enabled():
        return
    for b in plan:
        _flight.record("collective", "grad_bucket", bucket=b["bucket"],
                       bytes=b["bytes"], grads=b["grads"])
    attrs = {"buckets": len(plan),
             "bytes": sum(b["bytes"] for b in plan)}
    if dur_us is not None:
        attrs["dur_us"] = round(dur_us, 1)
    _flight.record("collective", "dist_step", **attrs)


# -- watchdog + telemetry instrumentation -------------------------------------
# every eager collective runs inside a named span so an installed watchdog
# (watchdog.install_watchdog) attributes hangs to the exact operation —
# the reference's per-CommTask start/end tracking
# (ref: comm_task_manager.h:37-57). Free when no watchdog is installed.
# The registry additionally gets per-collective call + payload-byte
# counters (the comm_task_manager bytes attribution); span latency lands
# in watchdog.span_seconds when a watchdog is installed.

import time as _time  # noqa: E402

from ..observability import flight as _flight  # noqa: E402
from ..observability import metrics as _om  # noqa: E402

_M_coll_calls = _om.counter(
    "collectives.calls_total", "Eager collective invocations by op")
_M_coll_bytes = _om.counter(
    "collectives.bytes_total",
    "Input tensor payload bytes entering eager collectives by op "
    "(best-effort: positional payload args only)")

# which positional arg(s) carry the INPUT payload per op — several
# collectives take their output buffer first (all_gather, scatter,
# reduce_scatter, alltoall), and counting that would inflate bytes with
# buffers no payload entered through
_PAYLOAD_ARGS = {
    "all_reduce": (0,), "all_gather": (1,), "broadcast": (0,),
    "reduce": (0,), "scatter": (1,), "reduce_scatter": (1,),
    "alltoall": (1,), "alltoall_single": (1,), "send": (0,),
}


def _payload_bytes(opname, args) -> int:
    """Concrete input-tensor bytes for one collective call (lists of
    tensors included — scatter/alltoall take them). Lazy
    (unmaterialized) fusion handles and payloads passed as kwargs are
    skipped rather than forced/guessed."""
    n = 0
    for i in _PAYLOAD_ARGS.get(opname, ()):
        if i >= len(args):
            continue
        a = args[i]
        for t in (a if isinstance(a, (list, tuple)) else (a,)):
            buf = getattr(t, "_buf", None)
            if buf is not None:
                n += int(getattr(buf, "nbytes", 0) or 0)
    return n


def _spanned(fn):
    opname = fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from .watchdog import collective_span
        g = kwargs.get("group")
        if not isinstance(g, Group):  # group may be passed positionally
            g = next((a for a in args if isinstance(a, Group)), None)
        gid = g.id if isinstance(g, Group) else 0
        want_flight = _flight.enabled()
        nbytes = 0
        if _om.enabled() or want_flight:
            nbytes = _payload_bytes(opname, args)
        if _om.enabled():
            _M_coll_calls.inc(op=opname)
            if nbytes:
                _M_coll_bytes.inc(nbytes, op=opname)
        if not want_flight:
            with collective_span(f"{opname}(group={gid})"):
                return fn(*args, **kwargs)
        # flight trail: op, payload bytes, host-observed duration — the
        # T3 overlap-efficiency input (ROADMAP item 3). NOTE duration is
        # dispatch-to-return on the host; device completion may lag.
        t0 = _time.perf_counter()
        with collective_span(f"{opname}(group={gid})"):
            out = fn(*args, **kwargs)
        _flight.record(
            "collective", opname, group=gid, bytes=nbytes,
            dur_us=round((_time.perf_counter() - t0) * 1e6, 1))
        return out
    return wrapper


all_reduce = _spanned(all_reduce)
all_gather = _spanned(all_gather)
all_gather_object = _spanned(all_gather_object)
broadcast = _spanned(broadcast)
broadcast_object_list = _spanned(broadcast_object_list)
reduce = _spanned(reduce)
scatter = _spanned(scatter)
scatter_object_list = _spanned(scatter_object_list)
reduce_scatter = _spanned(reduce_scatter)
alltoall = _spanned(alltoall)
alltoall_single = _spanned(alltoall_single)
send = _spanned(send)
recv = _spanned(recv)
barrier = _spanned(barrier)


class stream:
    """paddle.distributed.stream.* namespace parity (sync/calc-stream
    variants collapse on TPU: XLA owns scheduling)."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    broadcast = staticmethod(broadcast)
    reduce = staticmethod(reduce)
    scatter = staticmethod(scatter)
    alltoall = staticmethod(alltoall)
    reduce_scatter = staticmethod(reduce_scatter)
    send = staticmethod(send)
    recv = staticmethod(recv)
