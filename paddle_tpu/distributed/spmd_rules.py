"""Per-op SPMD sharding rules: the explicit propagation table.

ref: paddle/phi/infermeta/spmd_rules/ (~60 per-op rules, e.g.
matmul.cc:116 MatmulInferSpmd, flash_attention.cc, moe_gate_dispatch.cc)
and the registry in phi/core/distributed/auto_parallel/inferspmd_utils.h.
The TPU build leans on GSPMD for most propagation, but GSPMD cannot see
through Pallas kernels: a pallas_call under pjit with sharded operands
would be replicated (or mis-sharded). The rules here produce the
`shard_map` in/out PartitionSpecs that pin the intended decomposition —
the direct analog of the reference's InferSpmd (input dist_attrs ->
output dist_attrs + required reshards).

Two consumers:
- ops.yaml `spmd:` entries name a rule per op; the native OpRegistry
  carries the name and `get_rule(name)` resolves it (tested so every
  named rule exists).
- `shard_*` helpers below apply the three custom-kernel rules (flash
  attention, grouped matmul, MoE dispatch) through shard_map, asserting
  the collectives the rule implies (HLO-inspected in tests).

A rule is `fn(*arg_specs, **shape_kwargs) -> (in_specs, out_specs)`
over jax.sharding.PartitionSpec. Unknown/unsupported input placements
raise — the caller falls back to replicate-with-GSPMD, never a silent
wrong decomposition (SURVEY §7 hard-parts list: "missing rules must fall
back to replicate-with-warning, not crash").
"""
from __future__ import annotations

from typing import Callable, Dict

from jax.sharding import PartitionSpec as P

__all__ = ["get_rule", "register_rule", "list_rules",
           "shard_map_flash_attention", "shard_map_grouped_matmul",
           "shard_map_moe_dispatch"]

_RULES: Dict[str, Callable] = {}


def register_rule(name: str):
    def deco(fn):
        _RULES[name] = fn
        return fn
    return deco


def get_rule(name: str) -> Callable:
    if name not in _RULES:
        raise KeyError(
            f"no SPMD rule {name!r} (known: {sorted(_RULES)}); GSPMD "
            f"propagation is the fallback")
    return _RULES[name]


def list_rules():
    return sorted(_RULES)


# -- generic families -----------------------------------------------------

@register_rule("elementwise")
def elementwise(*in_specs):
    """Same-rank elementwise: dims merge across inputs; two inputs
    sharded DIFFERENTLY on the same dim conflict and raise (never a
    silent drop). ref: spmd_rules/elementwise.cc."""
    real = [s for s in in_specs if s is not None and len(s)]
    if not real:
        return tuple(in_specs), P()
    rank = max(len(s) for s in real)
    merged = [None] * rank
    for s in real:
        off = rank - len(s)  # right-align for broadcasting
        for i, d in enumerate(s):
            if d is None:
                continue
            j = off + i
            if merged[j] is not None and merged[j] != d:
                raise ValueError(
                    f"elementwise dim {j} sharded differently across "
                    f"inputs: {merged[j]} vs {d}")
            merged[j] = d
    return tuple(in_specs), P(*merged)


@register_rule("broadcast")
def broadcast(x_spec, *rest):
    return (x_spec, *rest), x_spec


@register_rule("reduction")
def reduction(x_spec, axis=None, keepdims=False):
    """Reduce: reduced dims' sharding drops (implies a psum when the
    reduced dim was sharded). ref: spmd_rules/reduction.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if axis is None:
        return (x_spec,), P()
    ax = axis if isinstance(axis, (list, tuple)) else [axis]
    out = [d for i, d in enumerate(dims) if i not in
           [a % len(dims) for a in ax]]
    if keepdims:
        out = [None if i in [a % len(dims) for a in ax] else d
               for i, d in enumerate(dims)]
    return (x_spec,), P(*out)


@register_rule("matmul")
def matmul(x_spec, y_spec):
    """[.., M, K] @ [.., K, N]: K sharded on both -> partial (psum);
    M/N pass through; batch dims merge across operands (conflict
    raises). ref: spmd_rules/matmul.cc:116."""
    xs = list(x_spec) if x_spec is not None else [None, None]
    ys = list(y_spec) if y_spec is not None else [None, None]
    if len(xs) < 2 or len(ys) < 2:
        raise ValueError(
            "matmul rule covers rank>=2 operands; annotate 1-D "
            "operands replicated (GSPMD handles the vector forms)")
    bx, by = xs[:-2], ys[:-2]
    rank = max(len(bx), len(by))
    batch = [None] * rank
    for bs in (bx, by):
        off = rank - len(bs)
        for i, d in enumerate(bs):
            if d is None:
                continue
            j = off + i
            if batch[j] is not None and batch[j] != d:
                raise ValueError(
                    f"matmul batch dim {j} sharded differently: "
                    f"{batch[j]} vs {d}")
            batch[j] = d
    m, kx = xs[-2], xs[-1]
    ky, n = ys[-2], ys[-1]
    if kx is not None and ky is not None and kx != ky:
        raise ValueError(
            f"matmul contraction dim sharded differently: {kx} vs {ky}")
    return (x_spec, y_spec), P(*batch, m, n)


@register_rule("transpose")
def transpose(x_spec, perm=None):
    if x_spec is None or perm is None:
        return (x_spec,), x_spec
    dims = list(x_spec) + [None] * (len(perm) - len(x_spec))
    return (x_spec,), P(*[dims[p] for p in perm])


@register_rule("reshape")
def reshape(x_spec):
    """Reshape keeps only the leading-dim sharding (general dim-mapping
    reshape propagation is GSPMD's job). ref: spmd_rules/reshape.cc."""
    if x_spec is None or not len(x_spec):
        return (x_spec,), x_spec
    return (x_spec,), P(x_spec[0])


@register_rule("concat")
def concat(*in_specs, axis=0):
    base = next((s for s in in_specs if s is not None), P())
    dims = list(base)
    if len(dims) > axis:
        dims[axis] = None  # concat dim cannot stay sharded
    return tuple(in_specs), P(*dims)


@register_rule("split")
def split(x_spec, axis=0):
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if len(dims) > axis:
        dims[axis] = None
    return (x_spec,), P(*dims)


@register_rule("softmax")
def softmax(x_spec):
    """Softmax dim (last) must be unsharded; leading dims pass through.
    ref: spmd_rules/softmax.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if dims and dims[-1] is not None:
        raise ValueError("softmax axis cannot be sharded")
    return (x_spec,), x_spec


@register_rule("embedding")
def embedding(ids_spec, w_spec):
    """Gather: ids batch sharding passes through; row-sharded tables
    need the mp allreduce the reference's c_embedding does.
    ref: spmd_rules/embedding.cc."""
    out = list(ids_spec) if ids_spec is not None else []
    hidden = None
    if w_spec is not None and len(w_spec) == 2:
        if w_spec[0] is not None:
            raise ValueError(
                "row-sharded embedding table needs VocabParallelEmbedding "
                "(masked gather + psum), not plain embedding")
        hidden = w_spec[1]
    return (ids_spec, w_spec), P(*out, hidden)


@register_rule("layer_norm")
def layer_norm(x_spec, *param_specs):
    """Normalized (trailing) dim unsharded; batch/seq pass through.
    ref: spmd_rules/layer_norm.cc."""
    if x_spec is not None and len(x_spec) and x_spec[-1] is not None:
        raise ValueError("layer_norm normalized dim cannot be sharded")
    return (x_spec, *param_specs), x_spec


@register_rule("rms_norm")
def rms_norm(x_spec, *param_specs):
    return layer_norm(x_spec, *param_specs)


@register_rule("batch_norm")
def batch_norm(x_spec, *rest):
    """Batch dims reduce into the channel stats: sharded batch implies a
    cross-device psum of the per-shard stats (data-parallel BN here
    computes per-shard batch stats, the DataParallel contract)."""
    return (x_spec, *rest), x_spec


@register_rule("dropout")
def dropout(x_spec, *rest):
    return (x_spec, *rest), x_spec


@register_rule("conv")
def conv(x_spec, w_spec, data_format="NCHW"):
    """Conv: batch sharding passes through, weights replicated, spatial
    dims unsharded (halo exchange is future work), input-channel
    sharding rejected (it would leave partial sums). data_format
    defaults to NCHW, matching the conv ops' own default — pass
    "NHWC"/"NLC"/"NDHWC" explicitly for channel-last layouts. Ranks 3-5
    (conv1d/2d/3d) are all validated."""
    if x_spec is not None and len(x_spec) >= 3:
        dims = list(x_spec)
        channel_last = data_format in ("NHWC", "NLC", "NWC", "NDHWC")
        ndim = len(dims)
        if channel_last:
            ch = ndim - 1
            spatial = tuple(range(1, ndim - 1))
        else:
            ch = 1
            spatial = tuple(range(2, ndim))
        if any(dims[i] is not None for i in spatial):
            raise ValueError(
                "spatially-sharded conv needs halo exchange — "
                "unsupported")
        if dims[ch] is not None:
            raise ValueError(
                "input-channel-sharded conv leaves partial sums "
                "(needs psum); reshard the channel dim first")
    if w_spec is not None and any(d is not None for d in w_spec):
        raise ValueError("conv weights must be replicated in this rule")
    out = list(x_spec) if x_spec is not None else [None] * 4
    return (x_spec, w_spec), P(*out)


@register_rule("cross_entropy")
def cross_entropy(logits_spec, label_spec):
    """Class dim unsharded (the mp-sharded variant is
    ParallelCrossEntropy); batch sharding implies psum of the mean."""
    if logits_spec is not None and len(logits_spec) and \
            logits_spec[-1] is not None:
        raise ValueError(
            "class-dim-sharded CE needs ParallelCrossEntropy "
            "(fleet.mp_layers), not plain cross_entropy")
    return (logits_spec, label_spec), P()


@register_rule("fused_ce")
def fused_ce(logits_spec, label_spec, *rest):
    return cross_entropy(logits_spec, label_spec)


@register_rule("rope")
def rope(x_spec, *rest):
    """Rotary embedding is positionwise over (seq, head_dim): any batch/
    head sharding passes; head_dim must be whole."""
    if x_spec is not None and len(x_spec) and x_spec[-1] is not None:
        raise ValueError("rope head_dim cannot be sharded")
    return (x_spec, *rest), x_spec


@register_rule("bias_act")
def bias_act(x_spec, *rest):
    return (x_spec, *rest), x_spec


@register_rule("scale")
def scale(x_spec, *rest):
    return (x_spec, *rest), x_spec


@register_rule("arg_reduce")
def arg_reduce(x_spec, axis=-1):
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if dims and dims[axis] is not None:
        raise ValueError("arg-reduce axis cannot be sharded")
    out = [d for i, d in enumerate(dims) if i != axis % len(dims)]
    return (x_spec,), P(*out)


# -- indexing / gather-scatter family -------------------------------------
# These return CORRECTED in_specs where a cheap local reshard makes the
# decomposition valid (the reference's InferSpmd contract: input
# dist_attrs -> required reshards + output dist_attrs); they raise only
# when the right answer is a different op.

@register_rule("gather")
def gather(x_spec, index_spec, axis=0):
    """Gather rows along `axis`: the gathered dim must be whole on every
    shard (a row-sharded table would need the masked-gather+psum path);
    index sharding lands on the output at the axis position, x's other
    dims pass through. ref: spmd_rules/gather.cc."""
    xs = list(x_spec) if x_spec is not None else []
    idx = list(index_spec) if index_spec is not None else [None]
    if xs:
        ax = axis % len(xs)
        if xs[ax] is not None:
            raise ValueError(
                "gather axis is sharded: use the masked-gather+psum "
                "decomposition (VocabParallelEmbedding pattern) or "
                "reshard the table first")
        out = xs[:ax] + idx + xs[ax + 1:]
    else:
        out = idx
    return (x_spec, index_spec), P(*out)


@register_rule("gather_nd")
def gather_nd(x_spec, index_spec, index_depth=1):
    """x's first `index_depth` dims are pointed into and must be whole;
    out = index batch dims + x trailing dims.
    ref: spmd_rules/gather_nd.cc."""
    xs = list(x_spec) if x_spec is not None else []
    idx = list(index_spec) if index_spec is not None else [None]
    fixed = list(xs)
    for d in range(min(index_depth, len(fixed))):
        fixed[d] = None  # indexed dims: reshard to whole
    # the coordinate-depth (last) dim of the index must be whole too —
    # a shard holding half of every coordinate tuple gathers garbage
    fixed_idx = P(*idx[:-1], None) if idx else index_spec
    out = idx[:-1] + fixed[index_depth:]
    return (P(*fixed) if xs else x_spec, fixed_idx), P(*out)


@register_rule("scatter")
def scatter(x_spec, index_spec, updates_spec=None, axis=0):
    """Scatter along `axis`: the written dim is whole per shard, and —
    since every shard then holds the FULL axis — each shard must apply
    ALL writes: index and the updates' axis dim reshard whole too;
    non-axis update dims follow x's. ref: spmd_rules/scatter.cc."""
    xs = list(x_spec) if x_spec is not None else []
    if not xs:
        return (x_spec, index_spec, updates_spec), x_spec
    ax = axis % len(xs)
    fixed = list(xs)
    fixed[ax] = None
    fixed_idx = P(*([None] * len(index_spec))) \
        if index_spec is not None else None
    fixed_upd = None
    if updates_spec is not None:
        ud = list(fixed)  # non-axis dims co-sharded with x
        ud[ax] = None
        fixed_upd = P(*ud[:len(updates_spec)])
    return (P(*fixed), fixed_idx, fixed_upd), P(*fixed)


@register_rule("one_hot")
def one_hot(ids_spec, depth=None):
    """Output appends an UNSHARDED class dim to the index dims.
    ref: spmd_rules/one_hot.cc."""
    out = list(ids_spec) if ids_spec is not None else []
    return (ids_spec,), P(*out, None)


# -- shape-manipulation family --------------------------------------------

@register_rule("slice")
def slice_rule(x_spec, axes=()):
    """Sliced dims lose their sharding (a shard can't know which rows of
    a sliced range it owns without a gather); untouched dims pass.
    ref: spmd_rules/slice.cc sets sliced dims_mapping to -1."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    for a in axes:
        if len(dims):
            dims[a % len(dims)] = None
    fixed = P(*dims)
    return (fixed,), fixed


@register_rule("stack")
def stack(*in_specs, axis=0):
    """Inputs merge elementwise-style; the new stack dim is unsharded.
    ref: spmd_rules/stack.cc."""
    _, merged = elementwise(*in_specs)
    dims = list(merged) if merged is not None else []
    ax = axis % (len(dims) + 1)
    return tuple(in_specs), P(*dims[:ax], None, *dims[ax:])


@register_rule("tile")
def tile(x_spec, repeats=()):
    """Tiled dims (repeat>1) lose sharding — each shard would need its
    neighbours' rows to build the repetition; repeat==1 dims pass.
    numpy/paddle semantics: a short `repeats` aligns to the TRAILING
    dims (jnp.tile pads repeats with leading 1s); extra repeats prepend
    new dims. ref: spmd_rules/tile.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    reps = list(repeats)
    rank = max(len(reps), len(dims))
    out = [None] * (rank - len(dims)) + dims          # right-align x
    reps_full = [1] * (rank - len(reps)) + reps       # right-align reps
    for i, r in enumerate(reps_full):
        if r != 1:
            out[i] = None
    fixed_in = P(*out[rank - len(dims):]) if dims else x_spec
    return (fixed_in,), P(*out)


@register_rule("pad")
def pad(x_spec, padded_dims=()):
    """Padded dims lose sharding (the shard holding the edge would need
    to know it's the global edge); others pass.
    ref: spmd_rules/pad.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    for d in padded_dims:
        if len(dims):
            dims[d % len(dims)] = None
    fixed = P(*dims)
    return (fixed,), fixed


@register_rule("squeeze")
def squeeze(x_spec, axis=None):
    """Removed size-1 dims can never be sharded; remaining shardings
    keep their dims. ref: spmd_rules/squeeze.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if axis is None:
        return (x_spec,), x_spec  # shape-dependent: GSPMD handles
    ax = axis if isinstance(axis, (tuple, list)) else [axis]
    drop = {a % len(dims) for a in ax}
    return (x_spec,), P(*[d for i, d in enumerate(dims)
                          if i not in drop])


@register_rule("unsqueeze")
def unsqueeze(x_spec, axis=0):
    """New size-1 dim is unsharded; existing shardings shift.
    ref: spmd_rules/unsqueeze.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    ax = axis % (len(dims) + 1)
    return (x_spec,), P(*dims[:ax], None, *dims[ax:])


@register_rule("flatten")
def flatten(x_spec, start_axis=0, stop_axis=-1):
    """A collapsed [a, b, c] group keeps the LEADING dim's sharding iff
    the trailing members are unsharded (rows stay contiguous per shard);
    otherwise the group replicates. ref: spmd_rules/flatten.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    n = len(dims)
    lo, hi = start_axis % n, stop_axis % n
    group = dims[lo:hi + 1]
    keep = group[0] if all(d is None for d in group[1:]) else None
    fixed_in = dims[:lo] + [group[0] if keep is not None else None] \
        + [None] * (len(group) - 1) + dims[hi + 1:]
    out = dims[:lo] + [keep] + dims[hi + 1:]
    return (P(*fixed_in),), P(*out)


@register_rule("expand_as")
def expand_as(x_spec, y_spec=None, target_rank=None):
    """Right-align x into the target rank; broadcast (new) dims take the
    target's sharding — each shard materializes only its slice of the
    broadcast, which is free. ref: spmd_rules/expand_as.cc."""
    xs = list(x_spec) if x_spec is not None else []
    if y_spec is not None:
        out = list(y_spec)
    elif target_rank is not None:
        out = [None] * target_rank
    else:
        raise ValueError(
            "expand_as rule needs the target's spec or rank: returning "
            "x's spec unchanged would shard the wrong dims after a "
            "rank-growing broadcast (specs bind leading dims; "
            "broadcasting aligns trailing) — fall back to GSPMD")
    off = len(out) - len(xs)
    for i, d in enumerate(xs):
        if d is not None:
            out[off + i] = d  # x's sharding wins on shared dims
    return (x_spec, y_spec), P(*out)


@register_rule("cast")
def cast(x_spec):
    """Dtype-only: placement passes through untouched.
    ref: spmd_rules/cast.cc."""
    return (x_spec,), x_spec


@register_rule("add_n")
def add_n(*in_specs):
    """Sum of same-shape tensors: elementwise merge.
    ref: spmd_rules/add_n.cc."""
    return elementwise(*in_specs)


@register_rule("where")
def where(c_spec, x_spec=None, y_spec=None):
    """Three-way elementwise merge. ref: spmd_rules/where.cc."""
    return elementwise(c_spec, x_spec, y_spec)


@register_rule("triu")
def triu(x_spec):
    """Positionwise mask over the last two dims: any sharding passes
    (the iota offset is shard-local arithmetic). ref:
    spmd_rules/triu.cc."""
    return (x_spec,), x_spec


# -- scan / norm family ----------------------------------------------------

@register_rule("cumsum")
def cumsum(x_spec, axis=0):
    """The scanned dim carries a prefix dependency across shards: it
    must be whole (reshard in), other dims pass.
    ref: spmd_rules/cumsum.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if dims:
        dims[axis % len(dims)] = None
    fixed = P(*dims)
    return (fixed,), fixed


@register_rule("p_norm")
def p_norm(x_spec, axis=None, keepdims=False):
    """Reduction semantics: reduced dims drop (partial per-shard norms
    combine via the psum GSPMD inserts — valid because sum-of-powers
    composes). ref: spmd_rules/p_norm.cc."""
    return reduction(x_spec, axis=axis, keepdims=keepdims)


@register_rule("logsumexp")
def logsumexp(x_spec, axis=None, keepdims=False):
    """ref: spmd_rules/logsumexp.cc — reduction-shaped propagation."""
    return reduction(x_spec, axis=axis, keepdims=keepdims)


@register_rule("squared_l2_norm")
def squared_l2_norm(x_spec):
    """Full reduce to a replicated scalar, any input sharding legal (the
    per-shard partial sums psum) — the grad-clip hot path the reference
    gives an explicit rule (spmd_rules/squared_l2_norm.cc) precisely so
    clip never forces a parameter all-gather."""
    return (x_spec,), P()


@register_rule("swiglu")
def swiglu(x_spec, y_spec=None):
    """Paired form silu(x)*y: elementwise merge (tp-sharded last dim is
    the mp_layers decomposition and passes). Packed single-input form
    splits the last dim in half, so ITS last dim must be whole.
    ref: spmd_rules/swiglu.cc."""
    if y_spec is not None:
        return elementwise(x_spec, y_spec)
    if x_spec is not None and len(x_spec) and x_spec[-1] is not None:
        raise ValueError(
            "packed swiglu halves its last dim: a sharded last dim "
            "interleaves gate/up across shards — pass gate and up "
            "separately (paired form) for tp")
    return (x_spec, None), x_spec


@register_rule("normalize")
def normalize(x_spec, axis=1):
    """F.normalize divides by the p-norm reduced along `axis`: that dim
    must be whole per shard (per-shard norms would be wrong); other
    dims pass. Same shape in/out."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if dims:
        dims[axis % len(dims)] = None
    fixed = P(*dims)
    return (fixed,), fixed


@register_rule("glu")
def glu(x_spec, axis=-1):
    """glu splits `axis` in half (a·sigmoid(b)): a sharded split dim
    would interleave the halves across shards — reshard it whole; the
    output halves the dim but keeps the other shardings."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if dims:
        dims[axis % len(dims)] = None
    fixed = P(*dims)
    return (fixed,), fixed


@register_rule("c_softmax_with_cross_entropy")
def c_softmax_with_cross_entropy(logits_spec, label_spec=None):
    """The CLASS-SHARDED softmax CE (the reference's mp collective op,
    fluid/operators/collective/c_softmax_with_cross_entropy_op.cu):
    class dim MAY be sharded — the max/sum reduce over the mp axis —
    and the loss keeps only the batch dims' sharding."""
    dims = list(logits_spec) if logits_spec is not None else [None]
    return (logits_spec, label_spec), P(*dims[:-1])


@register_rule("moe_combine")
def moe_combine(tokens_spec, gate_spec=None):
    """Inverse of moe_dispatch: the all-to-all returning expert outputs
    to their source rank; token sharding passes through.
    ref: spmd_rules/moe_combine.cc."""
    return (tokens_spec, gate_spec), tokens_spec


@register_rule("topk")
def topk(x_spec, axis=-1):
    """Selection along `axis` needs the whole dim per shard; other dims
    pass; values and indices share the output spec.
    ref: spmd_rules/topk.cc."""
    if x_spec is None:
        return (None,), (None, None)
    dims = list(x_spec)
    if dims:
        dims[axis % len(dims)] = None
    fixed = P(*dims)
    return (fixed,), (fixed, fixed)


@register_rule("argsort")
def argsort(x_spec, axis=-1):
    """Sorting a sharded dim would need a distributed sort network:
    reshard the axis whole; others pass. ref: spmd_rules/argsort.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    if dims:
        dims[axis % len(dims)] = None
    fixed = P(*dims)
    return (fixed,), fixed


@register_rule("take_along_axis")
def take_along_axis(x_spec, index_spec, axis=0):
    """Pointwise gather along `axis`: x's axis dim must be whole (any
    index row may point anywhere in it); the output has the INDEX's
    shape and inherits the index's sharding wholesale — an axis-sharded
    index is fine, each shard computes its own slice of the output.
    ref: spmd_rules/take_along_axis.cc."""
    xs = list(x_spec) if x_spec is not None else []
    idx = list(index_spec) if index_spec is not None else []
    if not xs:
        return (x_spec, index_spec), index_spec
    ax = axis % len(xs)
    # consistency: non-axis dims of x CO-SHARD with the index (each
    # shard must hold exactly the x rows its index rows point into);
    # the axis dim of x is whole; the output has the index's shape and
    # sharding
    fixed = [None if i == ax else (idx[i] if i < len(idx) else None)
             for i in range(len(xs))]
    return (P(*fixed), index_spec), index_spec


@register_rule("roll")
def roll(x_spec, axes=()):
    """Rolled dims wrap across shard boundaries: reshard them whole;
    untouched dims pass. ref: spmd_rules/... (roll ships in the
    reference's rule set as a shifted-layout op)."""
    return slice_rule(x_spec, axes=axes)


@register_rule("unbind")
def unbind(x_spec, axis=0):
    """Split into per-index views along `axis`: the unbound dim must be
    whole; each output drops it. ref: spmd_rules/unbind.cc."""
    if x_spec is None:
        return (None,), None
    dims = list(x_spec)
    ax = axis % len(dims) if dims else 0
    fixed = list(dims)
    if fixed:
        fixed[ax] = None
    out = [d for i, d in enumerate(fixed) if i != ax]
    return (P(*fixed),), P(*out)


# -- custom-kernel rules (the Pallas ops GSPMD cannot see through) --------

@register_rule("flash_attention")
def flash_attention(q_spec, k_spec, v_spec):
    """[B, L, H, D]: batch and head sharding decompose freely (each
    shard runs full attention over its rows); L-sharded inputs must go
    to ring attention (distributed.ring_attention) and D-sharded is
    invalid. ref: spmd_rules/flash_attention.cc."""
    for s in (q_spec, k_spec, v_spec):
        if s is None or len(s) != 4:
            continue
        if s[1] is not None:
            raise ValueError(
                "sequence-sharded flash attention must use "
                "ring_attention (context parallelism), not the dense "
                "kernel")
        if s[3] is not None:
            raise ValueError("head_dim cannot be sharded")
    base = q_spec if q_spec is not None else P(None, None, None, None)
    return (base, base, base), base


@register_rule("grouped_matmul")
def grouped_matmul(lhs_spec, rhs_spec, gs_spec=None):
    """lhs [T, K] x rhs [E, K, N]: expert-sharded rhs requires
    token-resharding by expert (the ep alltoall) BEFORE the kernel, so
    inside the kernel rhs must be whole per shard; token rows shard
    freely when every shard sees all experts. ref: the CUTLASS grouped
    GEMM's dispatch contract (fused_moe_kernel.cu)."""
    if rhs_spec is not None and len(rhs_spec) == 3:
        if rhs_spec[1] is not None or rhs_spec[2] is not None:
            raise ValueError("grouped_matmul K/N dims cannot be sharded")
        if rhs_spec[0] is not None and lhs_spec is not None and \
                lhs_spec[0] is not None:
            raise ValueError(
                "tokens and experts sharded together: dispatch tokens "
                "to their expert shard first (moe_dispatch alltoall)")
    out = P(lhs_spec[0] if lhs_spec is not None and len(lhs_spec)
            else None, None)
    return (lhs_spec, rhs_spec, gs_spec), out


@register_rule("moe_dispatch")
def moe_dispatch(tokens_spec, gate_spec=None):
    """Token-sharded input + expert-sharded FFN: the dispatch is an
    all-to-all over the ep axis (the reference's global_scatter), the
    combine its inverse. ref: spmd_rules/moe_gate_dispatch.cc."""
    return (tokens_spec, gate_spec), tokens_spec


# -- shard_map appliers for the custom kernels ----------------------------

def shard_map_flash_attention(mesh, q, k, v, *, batch_axis=None,
                              head_axis=None, causal=False, scale=None,
                              dropout_p=0.0, seed=None):
    """Run flash attention decomposed per the `flash_attention` rule:
    batch on ``batch_axis``, heads on ``head_axis`` — zero collectives
    in the forward (each shard is a full attention over its slice),
    which the HLO test asserts."""
    import jax

    from ..ops.pallas.flash_attention import flash_attention as _fa

    spec = P(batch_axis, None, head_axis, None)
    in_specs, out_spec = get_rule("flash_attention")(spec, spec, spec)

    def local(q_, k_, v_):
        return _fa(q_, k_, v_, causal, scale, dropout_p, seed)

    from jax import shard_map
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=out_spec, check_vma=False)(q, k, v)


def shard_map_grouped_matmul(mesh, lhs, rhs, group_sizes, *,
                             token_axis=None):
    """Grouped matmul with token rows sharded over ``token_axis`` and
    experts replicated (the `grouped_matmul` rule's collective-free
    decomposition). group_sizes must be per-shard counts."""
    from ..ops.pallas.grouped_matmul import grouped_matmul as _gmm

    lhs_spec = P(token_axis, None)
    in_specs, out_spec = get_rule("grouped_matmul")(
        lhs_spec, P(None, None, None), P(None))

    def local(l_, r_, gs_):
        return _gmm(l_, r_, gs_)

    from jax import shard_map
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=out_spec, check_vma=False)(
        lhs, rhs, group_sizes)


def shard_map_moe_dispatch(mesh, tokens, gate_w, w_in, w_out, *, top_k,
                           capacity, act, ep_axis):
    """MoE forward with experts sharded over ``ep_axis``: tokens
    re-shard to their expert's device via the alltoall the rule implies
    (tested by HLO inspection for all-to-all, matching the reference's
    global_scatter contract)."""
    import jax

    from ..incubate.moe_dispatch import moe_forward_indices

    # pin expert-sharded weights AND token-sharded input/output per the
    # registered moe_dispatch rule: with both ends fixed, either GSPMD
    # moves tokens (all-to-all, the global_scatter contract) or it would
    # have to all-gather the full expert weights — the HLO test forbids
    # weight-shaped all-gathers, so the memory-saving decomposition is
    # what ships. (Unlike the other appliers this one constrains a
    # GSPMD program rather than shard_map-ing: the dispatch gather is
    # data-dependent, which GSPMD lowers to the alltoall directly.)
    from jax.sharding import NamedSharding
    (tok_spec, _), out_spec = get_rule("moe_dispatch")(P(ep_axis, None))
    tok = jax.lax.with_sharding_constraint(
        tokens, NamedSharding(mesh, tok_spec))
    wi = jax.lax.with_sharding_constraint(
        w_in, NamedSharding(mesh, P(ep_axis, None, None)))
    wo = jax.lax.with_sharding_constraint(
        w_out, NamedSharding(mesh, P(ep_axis, None, None)))
    out = moe_forward_indices(tok, gate_w, wi, wo, top_k, capacity, act)
    y = out[0] if isinstance(out, tuple) else out
    y = jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, out_spec))
    return (y,) + tuple(out[1:]) if isinstance(out, tuple) else y
