"""Static auto-parallel planner v1: cost model + mesh/strategy search.

ref: python/paddle/distributed/auto_parallel/static/engine.py:100 (the
Engine's completion -> partition -> reshard pipeline is GSPMD here), and
static/cost/ + static/cluster.py — the reference prices each candidate
distributed program with per-op FLOPs/bytes models over a cluster
description, prunes infeasible ones, and picks the cheapest. This
planner does the TPU-native equivalent:

1. enumerate mesh factorizations of n_devices over (dp, fsdp, mp) and —
   when ``max_pp`` allows — a pipeline axis pp (the reference prices
   pipeline candidates through its schedule passes,
   ref: passes/pipeline_scheduler_pass/ + static/cost/);
2. price each with a roofline model — MXU time from model FLOPs,
   ICI time per axis from the collective bytes its sharding implies
   (dp: grad allreduce; fsdp: param allgather fwd+bwd + grad
   reduce-scatter; mp: per-layer activation allreduces; pp: boundary
   p2p bytes plus a bubble factor REPLAYED from the repo's own
   1F1B / ZB-H1 schedule simulators — the cheaper schedule wins and is
   recorded on the candidate);
3. prune configs whose per-chip memory (params + grads + optimizer
   state + activation checkpoints, with pipeline in-flight accounting)
   exceeds the HBM budget — the compile-free OOM verdict (the
   reference's prune-by-memory, auto_tuner/prune.py);
4. (optional) hand the top-k survivors to the auto_tuner trial runner,
   which compiles and TIMES each candidate (distributed/auto_tuner/
   runner.py) — measurement beats modeling for the final pick.

The cluster description (chip FLOP/s, ICI GB/s, HBM bytes) defaults to
v5e and is overridable — the analog of static/cluster.py.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

__all__ = ["Cluster", "ModelProfile", "PlanCandidate", "Planner",
           "profile_model", "detect_cluster"]


@dataclass
class Cluster:
    """ref: auto_parallel/static/cluster.py — the device description the
    cost model prices against. Defaults: one TPU v5e pod slice."""
    chip_flops: float = 197e12          # bf16 peak per chip
    ici_bandwidth: float = 45e9         # bytes/s per link direction
    hbm_bytes: float = 16e9
    mfu_ceiling: float = 0.6            # realistic matmul efficiency
    ici_latency: float = 5e-6           # per-collective launch latency
    mp_min_width: int = 512             # hidden/mp below this starves
    # the MXU (128-wide systolic tiles + pipelining need fat matmuls);
    # compute efficiency scales ~ linearly with shard width under it


# Known accelerator table (peak bf16 FLOP/s, HBM bytes, ICI GB/s per
# link direction); device_kind substring -> spec. The reference loads
# its cluster description from a JSON topology file or auto-detects
# (ref: auto_parallel/static/cluster.py); here jax.devices() is the
# source of truth and this table fills in what PJRT doesn't report.
_CHIP_TABLE = [
    ("v5 lite", (394e12 / 2, 16e9, 45e9)),   # v5e (197 bf16 via 394/2)
    ("v5e", (197e12, 16e9, 45e9)),
    ("v5p", (459e12, 95e9, 100e9)),
    ("v6", (918e12, 32e9, 90e9)),
    ("v4", (275e12, 32e9, 50e9)),
    ("v3", (123e12, 32e9, 70e9)),
]


def detect_cluster(probe: bool = False) -> Cluster:
    """Build a Cluster from the live runtime instead of a hand-filled
    dataclass (ref: static/cluster.py auto-detection): device_kind maps
    through the chip table, HBM comes from PJRT memory_stats when the
    platform reports it, and ``probe=True`` additionally MEASURES chip
    FLOP/s (one timed bf16 matmul) and per-collective latency (a timed
    psum on multi-device runtimes) — measurement beats tables on
    unknown hardware, and the offline fallback is the defaults."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    kind = getattr(devs[0], "device_kind", "").lower()
    flops, hbm, ici = next(
        (spec for sub, spec in _CHIP_TABLE if sub in kind),
        (None, None, None))
    c = Cluster()
    if flops is not None:
        c.chip_flops, c.hbm_bytes, c.ici_bandwidth = flops, hbm, ici
    try:
        stats = devs[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            c.hbm_bytes = float(stats["bytes_limit"])
    except Exception:
        pass
    if probe:
        # matmul peak probe: a 2048^3 bf16 dot (~17 GFLOP) timed after
        # warm-up; peak ~= measured / typical large-matmul efficiency
        n = 2048
        x = jnp.ones((n, n), jnp.bfloat16)
        f = jax.jit(lambda a, b: a @ b)
        jax.block_until_ready(f(x, x))
        t0 = time.perf_counter()
        for _ in range(4):
            y = f(x, x)
        jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / 4
        measured = 2 * n ** 3 / dt
        if flops is None:  # unknown chip (e.g. CPU): trust the probe
            c.chip_flops = measured / max(c.mfu_ceiling, 1e-6)
        if len(devs) > 1:
            from jax.sharding import Mesh, PartitionSpec as P
            mesh = Mesh(np.array(devs), ("x",))
            from jax import shard_map
            g = jax.jit(shard_map(
                lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                in_specs=P(), out_specs=P()))
            z = jnp.ones((8,), jnp.float32)
            jax.block_until_ready(g(z))
            t0 = time.perf_counter()
            for _ in range(8):
                w = g(z)
            jax.block_until_ready(w)
            c.ici_latency = max((time.perf_counter() - t0) / 8, 1e-7)
    return c


@dataclass
class ModelProfile:
    """What the cost model needs to know about one training step."""
    param_bytes: int                    # total parameter bytes
    flops_per_step: float               # fwd+bwd+update FLOPs
    batch_tokens: int = 1
    hidden: int = 1                     # activation width (mp comm unit)
    layer_count: int = 1                # mp comm multiplier
    act_dtype_bytes: int = 2
    bytes_per_param_state: float = 10.0  # grad + opt state per param byte
    # (bf16 grads 1x + f32 moments 8 bytes/2-byte-param => ~10x is AdamW
    # with fp32 state; SGD-momentum would be ~4)
    # -- context parallelism (ring attention) --
    # tokens per SAMPLE: dp/fsdp split samples, cp splits WITHIN one —
    # the axis that matters when one sequence is the whole batch
    seq_len: int = 1
    # -- expert parallelism (MoE) --
    # bytes of expert FFN params (shardable over ep on top of fsdp)
    moe_expert_param_bytes: int = 0
    moe_layer_count: int = 0            # alltoall pairs per step

    @property
    def activation_bytes(self) -> float:
        """Standard transformer footprint ~12 tensors of
        [tokens, hidden] live per layer."""
        return (12.0 * self.layer_count * self.batch_tokens *
                self.hidden * self.act_dtype_bytes)


def profile_model(model, batch_tokens: int,
                  layer_count: Optional[int] = None) -> ModelProfile:
    """Build a ModelProfile from a live Layer: params from the module
    tree, FLOPs from the 6·N·tokens transformer estimate (the standard
    fwd+bwd accounting; ref static_op_benchmark.json's role is pricing
    sanity, not exactness), activations ~ 12·tokens·hidden guess."""
    import numpy as np
    n_params = 0
    p_bytes = 0
    widths: List[int] = []
    for p in model.parameters():
        size = int(np.prod(p.shape)) if len(p.shape) else 1
        n_params += size
        p_bytes += size * p._data.dtype.itemsize
        if len(p.shape) >= 2:
            widths.append(int(p.shape[-1]))
    hidden = int(np.median(widths)) if widths else 1
    layers = layer_count
    if layers is None:
        # count distinct numbered blocks in param names as the proxy
        import re
        idx = {m.group(1) for n, _ in model.named_parameters()
               for m in [re.search(r"(?:^|\.)(\d+)\.", n)] if m}
        layers = max(len(idx), 1)
    return ModelProfile(
        param_bytes=p_bytes,
        flops_per_step=6.0 * n_params * batch_tokens,
        batch_tokens=batch_tokens,
        hidden=hidden,
        layer_count=layers,
    )


@dataclass
class PlanCandidate:
    dp: int
    fsdp: int
    mp: int
    pp: int = 1
    cp: int = 1                   # ring-attention context parallel
    ep: int = 1                   # MoE expert parallel
    schedule: str = ""            # "1f1b" | "zb_h1" when pp > 1
    bubble_fraction: float = 0.0
    est_step_time: float = 0.0
    est_mem_bytes: float = 0.0
    feasible: bool = True
    reason: str = ""
    measured_items_per_s: Optional[float] = None

    @property
    def mesh_shape(self) -> Tuple[int, int, int]:
        return (self.dp, self.fsdp, self.mp)

    @property
    def full_shape(self) -> Tuple[int, int, int, int]:
        return (self.dp, self.fsdp, self.mp, self.pp)

    @property
    def six_axis_shape(self):
        return (self.dp, self.fsdp, self.mp, self.pp, self.cp, self.ep)


def _ring_factor(n: int) -> float:
    return (n - 1) / n if n > 1 else 0.0


@functools.lru_cache(maxsize=None)
def _bubble_fractions(pp: int, micro: int) -> Tuple[float, float]:
    """(1F1B, ZB-H1) bubble fractions for a pp-stage pipeline with
    ``micro`` micro-batches, replayed through the repo's own schedule
    simulator (fleet/pipeline_zero_bubble.py) — the same event/dependency
    model the real schedules execute, not a closed-form guess."""
    from ..fleet.pipeline_zero_bubble import (
        one_f_one_b_schedule, simulate_schedule, zb_h1_schedule)

    busy = 3 * micro  # per-stage work slots: micro * (t_f + t_b + t_w)

    def frac(idle_by_stage):
        worst = max(idle_by_stage.values())
        return worst / (worst + busy)

    f1b = frac(simulate_schedule(
        {s: one_f_one_b_schedule(pp, s, micro) for s in range(pp)},
        fused_bw=True))
    zb = frac(simulate_schedule(
        {s: zb_h1_schedule(pp, s, micro) for s in range(pp)}))
    return f1b, zb


class Planner:
    """Search over (dp, fsdp, mp) factorizations of n_devices.

    ``plan()`` = analytic rank (+ memory prune); ``plan_measured()``
    additionally times the top-k with the auto_tuner trial runner and
    returns the measured winner — the reference's two-phase
    cost-model-then-trials flow (auto_tuner/tuner.py)."""

    def __init__(self, n_devices: int, cluster: Optional[Cluster] = None,
                 max_mp: Optional[int] = None, max_pp: int = 1,
                 micro_batches: Optional[int] = None,
                 schedules=None, max_cp: int = 1, max_ep: int = 1):
        self.n = n_devices
        self.cluster = cluster or Cluster()
        self.max_mp = max_mp or n_devices
        # cp/ep axes open only when the caller can realize them (ring
        # attention in the model / a MoE layer with expert sharding) —
        # the repo's above-parity features the planner can now price
        self.max_cp = max(int(max_cp), 1)
        self.max_ep = max(int(max_ep), 1)
        # pp candidates are enumerated only up to max_pp: the caller must
        # be able to REALIZE a pipeline plan (Engine gates this on its
        # pipeline executor's segmentation contract)
        self.max_pp = max(int(max_pp), 1)
        self.micro_batches = micro_batches  # default: 2*pp per candidate
        # which schedules the CALLER can execute: pp candidates are
        # priced with the best bubble among these and record the pick.
        # Default = the fleet's executable split-B/W schedules; the
        # Engine's compiled-GPipe executor passes ("gpipe",) so the plan
        # is priced with the fill-drain bubble it will actually get.
        self.schedules = tuple(schedules or ("1f1b", "zb_h1"))

    def candidates(self) -> List[PlanCandidate]:
        out = []
        n = self.n
        for pp in range(1, min(self.max_pp, n) + 1):
            if n % pp:
                continue
            n1 = n // pp
            for cp in range(1, min(self.max_cp, n1) + 1):
                if n1 % cp:
                    continue
                n2 = n1 // cp
                for ep in range(1, min(self.max_ep, n2) + 1):
                    if n2 % ep:
                        continue
                    nn = n2 // ep
                    for dp in range(1, nn + 1):
                        if nn % dp:
                            continue
                        rem = nn // dp
                        for fsdp in range(1, rem + 1):
                            if rem % fsdp:
                                continue
                            mp = rem // fsdp
                            if mp > self.max_mp:
                                continue
                            out.append(PlanCandidate(
                                dp=dp, fsdp=fsdp, mp=mp, pp=pp, cp=cp,
                                ep=ep))
        return out

    def _pick_schedule(self, pp: int, micro: int):
        """Best executable schedule for (pp, micro): replay 1F1B/ZB-H1
        through the repo's own simulator (the executable schedules in
        fleet/pipeline_zero_bubble.py); GPipe fill-drain closed form
        is (pp-1) idle slots around micro working slots per stage."""
        f1b, zb = _bubble_fractions(pp, micro)
        gp = (pp - 1) / (micro + pp - 1)
        options = {"1f1b": f1b, "zb_h1": zb, "gpipe": gp}
        return min(((s, options[s]) for s in self.schedules
                    if s in options), key=lambda kv: kv[1])

    def price(self, cand: PlanCandidate, prof: ModelProfile
              ) -> PlanCandidate:
        c = self.cluster
        micro = self.micro_batches or max(2 * cand.pp, 1)
        n_shard = cand.fsdp * cand.mp * cand.pp
        # the data axes can never split finer than the data: dp/fsdp
        # split SAMPLES, cp splits one sample's sequence — this is the
        # physics that makes cp the only way to scale a single long
        # sequence (ring attention, SURVEY §5 long-context)
        batch_samples = max(prof.batch_tokens // max(prof.seq_len, 1), 1)
        if cand.dp * cand.fsdp > batch_samples:
            cand.feasible = False
            cand.reason = (f"dp*fsdp={cand.dp * cand.fsdp} exceeds "
                           f"{batch_samples} batch sample(s)")
            return cand
        if cand.cp > 1 and prof.seq_len // cand.cp < 128:
            cand.feasible = False
            cand.reason = (f"cp={cand.cp} shards seq {prof.seq_len} "
                           f"below one flash tile (128)")
            return cand
        if cand.ep > 1 and (not prof.moe_layer_count
                            or not prof.moe_expert_param_bytes):
            # ep on a dense model would be a free (uncosted) axis that
            # shards nothing — reject rather than mis-rank
            cand.feasible = False
            cand.reason = "ep>1 but the model has no MoE experts"
            return cand
        # -- memory: params+grads+opt sharded by fsdp*mp, and by pp too
        # (each stage owns only its layers). Activations: per-layer
        # rematerialization keeps ONE layer's working set live, but the
        # remat CHECKPOINTS (one [tokens, hidden] boundary per layer,
        # batch split over dp*fsdp) are stored — pipeline stages store
        # them only for their own layers and in-flight micro-batches,
        # which is the memory lever pp has that fsdp doesn't: fsdp can
        # never shard a batch it can't split, pp shards the LAYERS.
        dense_bytes = prof.param_bytes - prof.moe_expert_param_bytes
        state_scale = 1 + prof.bytes_per_param_state
        # expert params additionally shard over ep — THE memory lever
        # of expert parallelism (the reference shards expert FFNs over
        # the ep group, moe_layer.py; dense params don't see ep)
        state_bytes = (dense_bytes * state_scale
                       + prof.moe_expert_param_bytes * state_scale
                       / cand.ep)
        act_live = prof.activation_bytes / max(prof.layer_count, 1)
        ckpt_all = (prof.layer_count * prof.batch_tokens * prof.hidden *
                    prof.act_dtype_bytes)
        ckpt = ckpt_all / (cand.dp * cand.fsdp * cand.cp)
        live = act_live / self.n
        if cand.pp > 1:
            # Pick the schedule FIRST (bubble replay needs only pp and
            # micro) so memory is priced with the schedule that will
            # actually run: 1F1B/ZB cap live checkpoints at the stage
            # depth, but GPipe's fill-drain holds every micro-batch's
            # stage checkpoints until backward starts — pricing a
            # gpipe-executed plan with min(pp, micro) under-counts ~2x
            # and the HBM prune admits plans the executor OOMs on.
            cand.schedule, cand.bubble_fraction = self._pick_schedule(
                cand.pp, micro)
            if cand.schedule == "gpipe":
                in_flight = micro
            else:
                in_flight = min(cand.pp, micro)
            ckpt = ckpt * in_flight / (micro * cand.pp)
            # the pipeline computes ONE micro-batch at a time per stage,
            # so the live working set shrinks with the micro count
            live = live / micro
        mem = state_bytes / n_shard + live + ckpt
        cand.est_mem_bytes = mem
        if mem > c.hbm_bytes:
            cand.feasible = False
            cand.reason = (f"est {mem/1e9:.1f}GB > HBM "
                           f"{c.hbm_bytes/1e9:.1f}GB")
        # -- compute: data/model-parallel FLOPs, degraded when mp
        # shards the hidden dim below the MXU-efficient width (the
        # known physics that makes tiny-model mp lose to dp even though
        # its comm bytes look small)
        width = max(prof.hidden / cand.mp, 1.0)
        mp_eff = min(1.0, width / c.mp_min_width)
        t_compute = prof.flops_per_step / self.n / \
            (c.chip_flops * c.mfu_ceiling * mp_eff)
        # -- pipeline bubble: schedule + fraction were picked in the
        # memory pass above (so memory matches the executed schedule)
        if cand.pp > 1:
            t_compute = t_compute / max(1.0 - cand.bubble_fraction, 1e-3)
        # -- communication per step (ring costs over ICI):
        bw = c.ici_bandwidth
        shard_param_bytes = prof.param_bytes / n_shard
        t_dp = 2 * shard_param_bytes * _ring_factor(cand.dp) / bw
        t_fsdp = 3 * (prof.param_bytes / (cand.mp * cand.pp)) * \
            _ring_factor(cand.fsdp) / bw
        # Megatron mp: two activation allreduces fwd + two bwd per layer
        # over this shard's [tokens, hidden] tensor (tokens split by
        # every data-splitting axis: dp, fsdp AND cp)
        mp_bytes = (4 * prof.layer_count *
                    (prof.batch_tokens / (cand.dp * cand.fsdp * cand.cp))
                    * prof.hidden * prof.act_dtype_bytes)
        t_mp = mp_bytes * _ring_factor(cand.mp) / bw
        # cp ring attention: per layer, (cp-1) ring hops rotate this
        # shard's K/V blocks fwd and again (with grads) bwd — 3 passes
        # of 2*[tokens_local, hidden] over ICI (ring_attention.py's
        # ppermute schedule)
        t_cp = 0.0
        if cand.cp > 1:
            tokens_local = prof.batch_tokens / (cand.dp * cand.fsdp *
                                                cand.cp)
            hop = 2 * tokens_local * prof.hidden * prof.act_dtype_bytes
            t_cp = 3 * prof.layer_count * (cand.cp - 1) * hop / bw
        # ep alltoall: dispatch + combine move this shard's tokens to
        # their experts and back, fwd and bwd (the reference's
        # global_scatter/global_gather pair per MoE layer); the DENSE
        # params see the ep group as plain data parallelism, so their
        # grads pay an extra allreduce over ep
        t_ep = 0.0
        if cand.ep > 1 and prof.moe_layer_count:
            tokens_local = prof.batch_tokens / (cand.dp * cand.fsdp *
                                                cand.cp)
            a2a = (tokens_local * prof.hidden * prof.act_dtype_bytes *
                   (cand.ep - 1) / cand.ep)
            t_ep = (3 * 2 * prof.moe_layer_count * a2a) / bw
            t_ep += 2 * (dense_bytes / n_shard) * \
                _ring_factor(cand.ep) / bw
        # pp boundary p2p: one [tokens_micro, hidden] activation fwd and
        # one grad bwd per stage boundary per micro-batch
        t_pp = 0.0
        if cand.pp > 1:
            tokens_micro = prof.batch_tokens / (cand.dp * cand.fsdp *
                                                cand.cp * micro)
            hop_bytes = tokens_micro * prof.hidden * prof.act_dtype_bytes
            t_pp = 2 * (cand.pp - 1) * micro * hop_bytes / bw
        # per-COLLECTIVE launch latency (ring transfers pipeline, so
        # the launch cost is ~independent of ring length): dp's grad
        # allreduce is one fused pair; fsdp gathers/scatters and mp
        # allreduces fire per layer — at toy scale this fixed cost is
        # why pure dp measures fastest
        lat = c.ici_latency
        t_lat = ((2 * lat if cand.dp > 1 else 0.0) +
                 (3 * prof.layer_count * lat if cand.fsdp > 1 else 0.0) +
                 (4 * prof.layer_count * lat if cand.mp > 1 else 0.0) +
                 (3 * prof.layer_count * (cand.cp - 1) * lat
                  if cand.cp > 1 else 0.0) +
                 (6 * prof.moe_layer_count * lat if cand.ep > 1
                  else 0.0) +
                 (2 * (cand.pp - 1) * micro * lat if cand.pp > 1
                  else 0.0))
        cand.est_step_time = (t_compute + t_dp + t_fsdp + t_mp + t_cp +
                              t_ep + t_pp + t_lat)
        return cand

    def plan(self, prof: ModelProfile, top_k: int = 1,
             realizable_fn: Optional[Callable] = None
             ) -> List[PlanCandidate]:
        """Rank feasible candidates by estimated step time.
        ``realizable_fn`` additionally prunes configs the caller's
        executor cannot run (e.g. pp plans whose block family doesn't
        split) — the single home of the realizability contract, shared
        by the Engine's analytic path and plan_measured."""
        priced = [self.price(c, prof) for c in self.candidates()]
        feas = [c for c in priced if c.feasible]
        if not feas:
            detail = "; ".join(
                f"dp{c.dp}/fsdp{c.fsdp}/mp{c.mp}: {c.reason}"
                for c in priced[:6])
            raise ValueError(
                f"no feasible parallel config for {self.n} devices "
                f"({detail}) — add devices or shrink the model/batch")
        if realizable_fn is not None:
            feas = [c for c in feas if realizable_fn(c)]
            if not feas:
                raise ValueError(
                    "no realizable parallel config: every feasible "
                    "candidate needs shardings the caller's executor "
                    "can't deliver (pp with fsdp/mp, or pp not dividing "
                    "the block family) — raise HBM, shrink the model, "
                    "or provide a mesh explicitly")
        feas.sort(key=lambda c: c.est_step_time)
        return feas[:top_k]

    def plan_measured(self, prof: ModelProfile, trial_fn: Callable,
                      top_k: int = 3,
                      realizable_fn: Optional[Callable] = None
                      ) -> PlanCandidate:
        """Time the analytic top-k with ``trial_fn(config_dict) ->
        items/s`` (build_trial_runner's contract); failures (OOM et al)
        are recorded and skipped like the reference's failed trials.
        ``realizable_fn`` prunes candidates the caller's executor cannot
        run BEFORE they occupy trial slots (otherwise 3 unrealizable pp
        plans would exhaust the trials while a realizable pp=1 plan sits
        just below the cut)."""
        cands = self.plan(prof, top_k=top_k, realizable_fn=realizable_fn)
        best = None
        for cand in cands:
            cfg = {"dp_degree": cand.dp, "fsdp_degree": cand.fsdp,
                   "mp_degree": cand.mp}
            if cand.pp > 1:
                cfg["pp_degree"] = cand.pp
                cfg["pp_schedule"] = cand.schedule
            try:
                cand.measured_items_per_s = float(trial_fn(cfg))
            except Exception as e:  # noqa: BLE001 — a failed trial is data
                cand.feasible = False
                cand.reason = f"trial failed: {type(e).__name__}: {e}"
                continue
            if best is None or cand.measured_items_per_s > \
                    best.measured_items_per_s:
                best = cand
        if best is None:
            raise RuntimeError("every trialed config failed")
        return best
