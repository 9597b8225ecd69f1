"""Engine: whole-program auto-parallel training orchestration.

ref: python/paddle/distributed/auto_parallel/static/engine.py:100
(Engine(model, loss, optimizer, metrics, strategy): .fit :1544 /
.evaluate / .predict; internally completion -> partition -> reshard ->
pass pipeline). The TPU analog: placements come from the model's
parameter shardings (or a shard_fn), and "partition + reshard insertion"
is GSPMD inside one jit — Engine drives data feeding, the compiled step,
eval loops, and checkpoints.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from ...core.tensor import Tensor
from ..dist_train import DistTrainStep

__all__ = ["Engine", "Strategy"]


@dataclass
class Strategy:
    """ref: auto_parallel/strategy.py Strategy (amp/recompute/sharding
    sub-configs as attribute bags). ``auto`` turns on the planner
    (ref: static engine auto_mode + static/cost planner): with
    enable=True and no mesh given, Engine prices every (dp, fsdp, mp)
    factorization with the roofline cost model and shards the model on
    the winner before compiling."""
    amp: dict = field(default_factory=dict)
    recompute: dict = field(default_factory=dict)
    sharding: dict = field(default_factory=dict)
    pipeline: dict = field(default_factory=dict)
    gradient_merge: dict = field(default_factory=dict)
    auto: dict = field(default_factory=dict)


class Engine:
    def __init__(self, model=None, loss=None, optimizer=None, metrics=None,
                 strategy: Optional[Strategy] = None, mesh=None,
                 shard_fn: Optional[Callable] = None,
                 data_sharding=None):
        self.model = model
        self.loss = loss
        self.optimizer = optimizer
        self.metrics = metrics or []
        self.strategy = strategy or Strategy()
        self.mesh = mesh
        self._data_sharding = data_sharding
        self._shard_fn = shard_fn
        if shard_fn is not None and mesh is not None:
            shard_fn(model, mesh)
        self._step: Optional[DistTrainStep] = None
        self._pending_plan_batch = None
        self.plan_choice = None
        self.recompute_report: Optional[dict] = None
        self.history: dict = {"loss": []}

    def _apply_strategy(self):
        """Strategy-driven passes (ref: passes/auto_parallel_{amp,
        sharding,gradient_merge}.py — completion/partition is GSPMD here;
        these knobs configure what the one compiled program does):
        amp -> bf16 weights (O2); sharding -> shard_optimizer with the
        configured stage; gradient_merge -> on-device micro-batch scan.
        recompute is the explicit fleet.utils.recompute segment wrapper
        (the reference's auto segment picker is a pass on its static IR;
        here segments are marked in model code)."""
        s = self.strategy
        amp = s.amp if isinstance(s.amp, dict) else vars(s.amp)
        if amp.get("enable"):
            dtype = str(amp.get("dtype", "bfloat16"))
            if dtype not in ("bfloat16", "bf16"):
                raise ValueError(
                    f"Engine amp dtype {dtype!r} is not supported on "
                    f"TPU — bfloat16 is the native fast dtype (fp16 "
                    f"has no hardware advantage here)")
            level = str(amp.get("level", "O1")).upper()
            if level == "O2":
                # O2 = master-weight cast (ref: passes/auto_parallel_fp16)
                self.model.bfloat16()
            else:
                # O1 keeps fp32 weights and autocasts per-op through the
                # white/black lists (ref: passes/auto_parallel_amp.py) —
                # the autocast context wraps forward so it applies both
                # eagerly and while the compiled step traces
                from ...amp import auto_cast
                inner_forward = self.model.forward

                def _amp_forward(*a, **kw):
                    with auto_cast(True, level="O1", dtype="bfloat16"):
                        return inner_forward(*a, **kw)

                self.model.forward = _amp_forward
        sh = s.sharding if isinstance(s.sharding, dict) else vars(s.sharding)
        if sh.get("enable") and self.mesh is not None:
            from ..api import shard_parameter
            from .api_ext import (ShardingStage1, ShardingStage2,
                                  ShardingStage3, shard_optimizer,
                                  _ShardOptimizer)
            # params must live on the same mesh as the sharded opt state
            for p in self.model.parameters():
                if p._dist_attr is None:
                    shard_parameter(p, self.mesh)
            if not isinstance(self.optimizer, _ShardOptimizer):
                stage = {1: ShardingStage1, 2: ShardingStage2,
                         3: ShardingStage3}[int(sh.get("stage", 1))]
                self.optimizer = shard_optimizer(self.optimizer,
                                                 stage(self.mesh))
        # gradient merge parsed BEFORE recompute: the memory probe must
        # model the k-way micro-batched program that actually runs
        gm = (s.gradient_merge if isinstance(s.gradient_merge, dict)
              else vars(s.gradient_merge))
        self._acc = int(gm.get("k_steps", 1)) if gm.get("enable") else 1
        rc = (s.recompute if isinstance(s.recompute, dict)
              else vars(s.recompute))
        if rc.get("enable"):
            target = rc.get("target_peak_bytes")
            min_repeat = int(rc.get("min_repeat", 2))
            if target is not None:
                self._memory_aware_recompute(int(target),
                                             min_repeat=min_repeat)
            else:
                self._auto_recompute(min_repeat=min_repeat)

    def _loss_fn(self):
        loss_fn = self.loss
        if hasattr(loss_fn, "forward"):  # a Layer criterion
            crit = loss_fn
            return lambda out, *labels: crit(out, *labels)
        return loss_fn

    def _probe_peak_bytes(self, batch) -> int:
        """Modeled peak live bytes of the train step for this batch:
        jaxpr liveness over a shape-only TRACE of the step (no XLA
        compile, no device allocation) via the static estimator — the
        decision metric for the memory-aware recompute pass (ref: the
        reference prices recompute candidates with its static memory
        cost model, not compiled binaries). The compiled
        ``memory_analysis()`` remains the deployment truth; XLA CPU's schedule-agnostic temp figure cannot
        see remat savings, the model can.

        Shape basis is GLOBAL: jaxpr avals carry unpartitioned logical
        shapes, so on an N-device mesh this is the whole-program figure
        (the target budget is interpreted on the same global basis; the
        report records the basis + mesh size for conversion)."""
        from .mem_estimator import estimate_peak_bytes
        opt = self.optimizer
        if hasattr(opt, "_inner"):
            opt = opt._inner
        probe = DistTrainStep(self.model, self._loss_fn(), opt,
                              data_sharding=self._data_sharding,
                              accumulate_steps=getattr(self, "_acc", 1))
        return int(estimate_peak_bytes(
            probe.trace_jaxpr(*batch, abstract=True)))

    def _memory_aware_recompute(self, target_peak_bytes: int,
                                min_repeat: int = 2):
        """Memory-model-driven segment picking (ref: passes/
        auto_parallel_recompute.py selects segments against a memory
        model, not a repeat-count heuristic): estimate the step's
        global-shape peak WITHOUT recompute; only when it exceeds the
        target are the repeated segments wrapped, and the peak is
        re-estimated to confirm the drop. Decision + both measurements
        land in ``self.recompute_report``."""
        n_dev = (self.mesh.to_jax_mesh().size
                 if self.mesh is not None else 1)
        basis = {"shape_basis": "global", "mesh_devices": n_dev,
                 "target_peak_bytes": int(target_peak_bytes)}
        batch = self._pending_plan_batch
        if batch is None:
            # no sample batch to measure against (explicit load()/
            # evaluate() path): fall back to the heuristic picker
            self._auto_recompute(min_repeat=min_repeat)
            self.recompute_report = {"mode": "heuristic-fallback",
                                     "reason": "no sample batch",
                                     **basis}
            return
        before = self._probe_peak_bytes(batch)
        if before <= target_peak_bytes:
            self.recompute_report = {
                "mode": "skipped", "peak_bytes": before, **basis}
            return
        wrapped = self._auto_recompute(min_repeat=min_repeat)
        if not wrapped:
            # nothing to wrap (no repeated block family): don't claim a
            # pass was applied, and don't pay a second trace
            self.recompute_report = {
                "mode": "no-segments", "peak_bytes": before, **basis}
            return
        after = self._probe_peak_bytes(batch)
        self.recompute_report = {
            "mode": "applied", "segments": len(wrapped),
            "peak_bytes_before": before, "peak_bytes_after": after,
            "met_target": after <= target_peak_bytes, **basis}

    def _auto_recompute(self, min_repeat: int = 2):
        """Auto segment picking (ref: passes/auto_parallel_recompute.py,
        which selects segments on the static IR): the largest-parameter
        family of repeated same-class sibling blocks (transformer
        layers, Sequential stages) becomes the recompute segment set;
        each member's forward is wrapped so its activations
        re-materialize during backward (jax.checkpoint under the
        compiled step). Returns the wrapped layers."""
        from ..fleet.utils.recompute import recompute as rc_fn

        best = None
        parents = [self.model] + [l for _, l in
                                  self.model.named_sublayers()]
        for parent in parents:
            groups: dict = {}
            for _, child in parent.named_children():
                groups.setdefault(type(child).__name__, []).append(child)
            for members in groups.values():
                if len(members) < min_repeat:
                    continue
                pc = sum(int(np.prod(p.shape)) for m in members
                         for p in m.parameters())
                if pc and (best is None or pc > best[0]):
                    best = (pc, members)
        if best is None:
            return []
        for layer in best[1]:
            if getattr(layer, "_recompute_wrapped", False):
                continue
            inner = layer.forward

            def fwd(*a, __inner=inner, __layer=layer, **kw):
                return rc_fn(__layer, *a, forward_fn=__inner, **kw)

            layer.forward = fwd
            layer._recompute_wrapped = True
        return best[1]

    def plan(self, sample_batch, n_devices: Optional[int] = None,
             cluster=None, trial_fn: Optional[Callable] = None):
        """Choose the parallel config (ref: static engine planner,
        static/cost/): profile the model, search mesh factorizations,
        build the winning mesh, and shard the model onto it. Called
        automatically by fit() when strategy.auto.enable and no mesh
        was given; callable directly for inspection (returns the
        chosen PlanCandidate). ``cluster``/``n_devices``/``trial_fn``
        may also be supplied through the strategy.auto dict so the
        fit() path can reach them. With a ``trial_fn(config_dict) ->
        items/s`` the analytic top-3 are timed and the measured winner
        is taken (ref: static engine's tuning mode)."""
        import jax
        import numpy as np

        from ..process_mesh import ProcessMesh
        from .planner import Planner, profile_model

        auto = (self.strategy.auto if isinstance(self.strategy.auto, dict)
                else vars(self.strategy.auto))
        n = n_devices or auto.get("n_devices") or len(jax.devices())
        cluster = cluster if cluster is not None else auto.get("cluster")
        if cluster is None:
            # no manual spec: detect from the live runtime (device-kind
            # table + PJRT memory stats; ref: static/cluster.py)
            from .planner import detect_cluster
            cluster = detect_cluster()
        trial_fn = trial_fn if trial_fn is not None \
            else auto.get("trial_fn")
        first = sample_batch[0] if isinstance(
            sample_batch, (tuple, list)) else sample_batch
        # shape only — np.asarray would pull the whole (possibly
        # device-resident) batch to the host
        shape = (first._data.shape if isinstance(first, Tensor)
                 else np.shape(first))
        batch_tokens = int(np.prod(shape[:2])) if len(shape) >= 2 \
            else int(shape[0])
        prof = profile_model(self.model, batch_tokens,
                             layer_count=auto.get("layer_count"))
        shard_fn = auto.get("shard_fn") or self._shard_fn
        # tensor parallelism needs model knowledge (column/row splits):
        # without a shard_fn the fallback only shards along fsdp, so an
        # mp>1 plan would be priced against memory it cannot realize
        max_mp = (auto.get("max_mp") if shard_fn is not None else 1)
        # the pipeline axis opens only when the model is realizable as
        # a pipeline (PipelineLayer segmentation contract) — a pp plan
        # the executor can't run would be worse than no plan
        max_pp = int(auto.get("max_pp", 1))
        fam_len = 0
        if max_pp > 1:
            from .engine_pp import detect_pipeline_split
            split = detect_pipeline_split(self.model)
            if split is None:
                max_pp = 1
            else:
                fam_len = len(split[1])
        planner = Planner(n, cluster=cluster, max_mp=max_mp,
                          max_pp=max_pp,
                          schedules=("gpipe",) if max_pp > 1 else None)
        def realizable(c):
            # v1 pipeline realization runs the non-pp axes as pure
            # data parallel (a pp plan that also assumed fsdp/mp
            # sharding would claim memory the executor can't
            # deliver), and the block family must split evenly
            # across the stages
            return c.pp == 1 or (c.fsdp == 1 and c.mp == 1
                                 and fam_len % c.pp == 0)

        # realizability filtering lives in Planner.plan (the single home
        # of the contract) so the analytic and measured paths can never
        # diverge; plan() ranks EVERY feasible candidate before the cut,
        # so a realizable pp=1 plan below the cheapest-16 is still found
        if trial_fn is not None:
            best = planner.plan_measured(prof, trial_fn,
                                         realizable_fn=realizable)
        else:
            best = planner.plan(prof, top_k=1,
                                realizable_fn=realizable)[0]
        self.plan_choice = best
        if best.pp > 1:
            # pipeline realization builds its own ("dp", "pp") mesh in
            # _ensure_step; no per-param shardings (blocks stack on pp)
            self.mesh = ProcessMesh(
                np.arange(n).reshape(n // best.pp, best.pp),
                dim_names=["dp", "pp"])
            return best
        dims = [d for d in best.mesh_shape]
        mesh = ProcessMesh(
            np.arange(n).reshape(dims), dim_names=["dp", "fsdp", "mp"])
        self.mesh = mesh
        if shard_fn is not None:
            # model-aware placements (tp column/row splits need model
            # knowledge, e.g. models.llama.shard_llama)
            shard_fn(self.model, mesh)
        else:
            from ..api import shard_parameter
            for p in self.model.parameters():
                shard_parameter(p, mesh, fsdp_axis="fsdp", fsdp_dim=0)
        return best

    def _ensure_step(self):
        if self._step is None:
            auto = (self.strategy.auto
                    if isinstance(self.strategy.auto, dict)
                    else vars(self.strategy.auto))
            if auto.get("enable") and self.mesh is None:
                if self._pending_plan_batch is None:
                    # building (and caching) an unplanned step here would
                    # silently disable auto sharding for the whole run
                    raise RuntimeError(
                        "strategy.auto needs a sample batch before the "
                        "step builds: call fit() first, or "
                        "Engine.plan(sample_batch) explicitly before "
                        "load()/evaluate()")
                self.plan(self._pending_plan_batch)
                # NOT cleared here: the memory-aware recompute pass in
                # _apply_strategy also probes against it; fit()/callers
                # clear it after _ensure_step returns
            self._apply_strategy()
            loss_fn = self._loss_fn()
            opt = self.optimizer
            if hasattr(opt, "_inner"):  # _ShardOptimizer: unwrap for step
                opt = opt._inner
            if self.plan_choice is not None and self.plan_choice.pp > 1:
                # realize the pipeline plan: compiled GPipe over the
                # ("dp", "pp") mesh (ref: static engine +
                # pipeline_scheduler_pass; the plan was also PRICED with
                # the GPipe fill-drain bubble — see plan()'s schedules
                # argument — so plan_choice.schedule tells the truth)
                if getattr(self, "_acc", 1) > 1:
                    raise NotImplementedError(
                        "gradient_merge with a pipeline plan is not "
                        "supported (v1): the pipeline already "
                        "micro-batches inside the step — drop "
                        "gradient_merge or cap max_pp to 1")
                from .engine_pp import PipelineTrainStep
                self._step = PipelineTrainStep(
                    self.model, loss_fn, opt, pp=self.plan_choice.pp,
                    n_devices=self.mesh.to_jax_mesh().size)
            else:
                self._step = DistTrainStep(
                    self.model, loss_fn, opt,
                    data_sharding=self._data_sharding,
                    accumulate_steps=getattr(self, "_acc", 1))
        return self._step

    # -- training (ref: engine.py fit :1544) --------------------------------
    def fit(self, train_data, epochs=1, steps_per_epoch=None, verbose=0,
            log_freq=10):
        step = None
        for epoch in range(epochs):
            for i, batch in enumerate(train_data):
                if steps_per_epoch is not None and i >= steps_per_epoch:
                    break
                batch = batch if isinstance(batch, (tuple, list)) else \
                    (batch,)
                if step is None:
                    # the planner needs a sample batch for its token
                    # count, so the step builds lazily at first batch
                    self._pending_plan_batch = batch
                    step = self._ensure_step()
                    self._pending_plan_batch = None  # don't pin the batch
                loss = step(*batch)
                self.history["loss"].append(float(loss))
                if verbose and i % log_freq == 0:
                    print(f"epoch {epoch} step {i}: "
                          f"loss {float(loss):.4f}")
        return self.history

    def evaluate(self, eval_data, steps=None):
        """Mean loss over eval batches (model in eval mode, no updates)."""
        was_training = self.model.training
        self.model.eval()
        losses = []
        try:
            for i, batch in enumerate(eval_data):
                if steps is not None and i >= steps:
                    break
                batch = batch if isinstance(batch, (tuple, list)) else \
                    (batch,)
                out = self.model(*[b if isinstance(b, Tensor) else
                                   _to_tensor(b) for b in batch[:-1]])
                loss = self.loss(out, _to_tensor(batch[-1]))
                losses.append(float(loss))
        finally:
            if was_training:
                self.model.train()
        return {"loss": float(np.mean(losses)) if losses else None}

    def predict(self, data, steps=None):
        was_training = self.model.training
        self.model.eval()
        outs = []
        try:
            for i, batch in enumerate(data):
                if steps is not None and i >= steps:
                    break
                batch = batch if isinstance(batch, (tuple, list)) else \
                    (batch,)
                outs.append(self.model(*[_to_tensor(b) for b in batch]))
        finally:
            if was_training:
                self.model.train()
        return outs

    # -- checkpoints (ref: engine save/load -> dist ckpt) -------------------
    def save(self, path: str):
        from ..checkpoint import save_state_dict
        state = {"model": self.model.state_dict()}
        if self._step is not None:
            state["opt"] = self._step.state_dict()
        save_state_dict(state, path)

    def load(self, path: str):
        from ..checkpoint import load_state_dict
        step = self._ensure_step()
        state = {"model": self.model.state_dict(),
                 "opt": step.state_dict()}
        load_state_dict(state, path)
        step.set_state_dict(state["opt"])


def _to_tensor(x):
    if isinstance(x, Tensor):
        return x
    import jax.numpy as jnp
    return Tensor(jnp.asarray(np.asarray(x)))
