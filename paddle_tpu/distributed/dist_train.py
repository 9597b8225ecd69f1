"""Sharded whole-training-step compiler — on the SOT capture engine.

The TPU-native replacement for the reference's hybrid-parallel training
machinery (ref: fleet/meta_parallel/* + auto_parallel/static/engine.py:100):
parameters carry NamedShardings (attached by shard_llama / shard_tensor),
and ONE captured executable of loss-fwd + backward + optimizer-update
compiles the whole dp x fsdp x tp program — XLA GSPMD inserts the ICI
collectives the reference issues manually through ProcessGroupNCCL
(all-gather for ZeRO-3 param shards, reduce-scatter of grads, allreduce
over dp). Optimizer state inherits each parameter's sharding, which *is*
sharding stage-1/2/3 depending on the placement rules used.

Since Fusion III's distributed round this class is a thin wrapper over
``jit.sot.CapturedStep`` in non-strict mode — the same signature
guards, LRU program cache, retrace/fallback counters and flight events
the single-chip ``jit.TrainStep`` rides (its bespoke ``jax.jit``
closure is gone) — plus two distributed specializations:

* **Gradient merge** (ref: passes/auto_parallel_gradient_merge.py):
  ``accumulate_steps`` micro-batches scanned inside the ONE captured
  program, grads accumulated in fp32.
* **Bucketed compute–collective overlap** (the T3 paper's fine-grained
  tracking-and-triggering): instead of gradient synchronization
  running as a serial epilogue after the full backward, grads group
  into ``FLAGS_dist_grad_bucket_bytes`` buckets in REVERSE-backward
  order and each bucket's all-reduce/reduce-scatter is emitted as its
  own first-class node in the captured DAG
  (``collective.bucketed_grad_sync``) — bucket k depends only on its
  own grads, so XLA's async collectives launch it while earlier
  layers are still differentiating. Per-bucket payload rides the
  flight recorder's collective events each step.
"""
from __future__ import annotations

import time as _time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.flags import flag_value
from ..core.tensor import Tensor
from ..jit.sot import CapturedStep
from ..profiler import RecordEvent

__all__ = ["DistTrainStep"]


class _DistCapturedStep(CapturedStep):
    """CapturedStep specialized for the sharded whole-step program:
    batch arrays device_put with the data sharding, freshly created
    optimizer slots co-sharded with their parameter (the ZeRO
    contract), gradient merge via an in-program scan, and bucketed
    gradient synchronization between backward and the optimizer tail."""

    def __init__(self, model, loss_fn, optimizer, data_sharding=None,
                 donate: bool = True, accumulate_steps: int = 1):
        super().__init__(model, loss_fn, optimizer, cast_loss_f32=True,
                         donate=donate, strict=False,
                         name="dist_train_step",
                         build_kind="dist_train_step")
        self.data_sharding = data_sharding
        self.accumulate_steps = max(int(accumulate_steps), 1)
        # bucket plans keyed by (bucket_bytes, trainable keys) — the
        # only inputs the plan depends on (grad shapes ARE the param
        # shapes). Keyed, not last-trace: a cached program replayed
        # after a flag round-trip must report ITS plan, not the most
        # recently traced one
        self._bucket_plans: Dict[tuple, List[Dict]] = {}

    # -- signature ---------------------------------------------------------
    def _signature(self, kind, arrays, n_ins, tkeys, scaler_statics=None):
        sig = super()._signature(kind, arrays, n_ins, tkeys,
                                 scaler_statics)
        if sig is None:
            return None
        # the bucket target shapes the traced program (bucket count +
        # barrier chain): a flag flip must retrace, not replay a stale
        # program — it joins the guards like every other trace input
        return sig + (("bucket_bytes",
                       int(flag_value("dist_grad_bucket_bytes") or 0)),)

    # -- batch plumbing ----------------------------------------------------
    def _arrays(self, values):
        out = super()._arrays(values)
        if out is not None and self.data_sharding is not None:
            out = [jax.device_put(r, self.data_sharding) for r in out]
        return out

    # -- optimizer state ---------------------------------------------------
    def _opt_state_for(self, p):
        """Slot state co-sharded with its parameter — the ZeRO contract
        (ref: dygraph_sharding_optimizer.py partitions state by param
        ownership; here ownership = the param's own placement).
        Scalar slots (beta pows) keep their shape and replicate."""
        opt = self.optimizer
        st = opt._states.get(id(p))
        if st is not None:
            return st
        st = opt._state_for(p)
        arr = p._data
        if hasattr(arr, "sharding"):
            st = {
                name: jax.device_put(v, arr.sharding)
                if getattr(v, "shape", None) == arr.shape else v
                for name, v in st.items()
            }
            opt._states[id(p)] = st
        return st

    # -- gradient merge ----------------------------------------------------
    def _value_and_grads(self, loss_of, train_p, buffers, batch, labels,
                         key):
        acc = self.accumulate_steps
        if acc <= 1:
            return super()._value_and_grads(loss_of, train_p, buffers,
                                            batch, labels, key)
        # split dim0 into [acc, -1] micro-batches and scan, averaging
        # grads (gradient merge, fully on-device)
        for arr in (*batch, *labels):
            if arr.shape[0] % acc:
                raise ValueError(
                    f"gradient merge: batch dim {arr.shape[0]} "
                    f"is not divisible by accumulate_steps="
                    f"{acc}; drop or pad the tail batch")
        micro_b = tuple(
            b.reshape((acc, b.shape[0] // acc) + b.shape[1:])
            for b in batch)
        micro_l = tuple(
            x.reshape((acc, x.shape[0] // acc) + x.shape[1:])
            for x in labels)
        keys = jax.random.split(key, acc)

        def scan_body(carry, xs):
            loss_sum, gsum, bufs = carry
            mb, lbls, k_ = xs
            (_, (l, nb)), g = jax.value_and_grad(
                loss_of, has_aux=True)(train_p, bufs, mb, lbls, k_)
            gsum = jax.tree.map(
                lambda a, b_: a + b_.astype(jnp.float32), gsum, g)
            return (loss_sum + l.astype(jnp.float32), gsum, nb), None

        # fp32 accumulators: merging k bf16 micro-grads in bf16 would
        # lose the low bits the merge exists to keep
        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), train_p)
        (loss_sum, grads, new_buffers), _ = jax.lax.scan(
            scan_body, (jnp.float32(0.0), g0, buffers),
            (micro_b, micro_l, keys))
        loss = loss_sum / acc
        grads = jax.tree.map(lambda g: g / acc, grads)
        return loss, grads, new_buffers

    # -- bucketed compute–collective overlap -------------------------------
    def current_bucket_plan(self) -> List[Dict]:
        """The plan of the program the CURRENT flag/trainable-set
        combination selects (empty before its first trace or with
        bucketing disabled)."""
        target = int(flag_value("dist_grad_bucket_bytes") or 0)
        return self._bucket_plans.get(
            (target, tuple(self._tkeys())), [])

    def _sync_grads(self, grads, tkeys):
        from jax.sharding import NamedSharding
        from . import collective as coll

        target = int(flag_value("dist_grad_bucket_bytes") or 0)
        plan_key = (target, tuple(tkeys))
        if target <= 0 or not grads:
            self._bucket_plans[plan_key] = []
            return grads
        # REVERSE-backward order: _Swap.params preserves registration
        # (forward) order, so its reverse approximates grad-retirement
        # order — the last layers' grads are ready first
        order = [k for k in reversed(list(self._swap.params))
                 if k in grads]
        sizes = []
        for k in order:
            g = grads[k]
            sizes.append((k, int(np.prod(g.shape))
                          * np.dtype(g.dtype).itemsize))
        buckets = coll.bucket_assignment(sizes, target)
        shardings = {}
        for k in order:
            sh = getattr(self._swap.params[k]._data, "sharding", None)
            if isinstance(sh, NamedSharding):
                shardings[k] = sh
        synced, plan = coll.bucketed_grad_sync(grads, buckets, shardings)
        self._bucket_plans[plan_key] = plan
        return synced

    # -- per-step telemetry ------------------------------------------------
    def step(self, inputs, labels=(), scaler=None):
        from ..observability import flight as _flight
        if not _flight.enabled():
            return super().step(inputs, labels, scaler)
        from . import collective as coll
        t0 = _time.perf_counter()
        loss = super().step(inputs, labels, scaler)
        if loss is not None:
            coll.journal_grad_buckets(
                self.current_bucket_plan(),
                dur_us=(_time.perf_counter() - t0) * 1e6)
        return loss


class DistTrainStep:
    """Compiled train step over (possibly sharded) params.

    loss_fn(outputs, *labels) -> scalar Tensor. Batch arrays should be
    device_put with their data sharding (Shard(0) on the dp axis) before the
    call — or pass `data_sharding` to have the step do it.
    """

    def __init__(self, model, loss_fn: Callable, optimizer,
                 data_sharding=None, donate: bool = True,
                 accumulate_steps: int = 1):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.data_sharding = data_sharding
        self._step = _DistCapturedStep(
            model, loss_fn, optimizer, data_sharding=data_sharding,
            donate=donate, accumulate_steps=accumulate_steps)
        self._swap = self._step._swap
        self._params = self._swap.params

    @property
    def accumulate_steps(self) -> int:
        return self._step.accumulate_steps

    @property
    def stats(self):
        """CapturedStep counters: compiles / cache_hits /
        captured_steps — the shared capture telemetry plane."""
        return self._step.stats

    def bucket_plan(self) -> List[Dict]:
        """The gradient-bucket plan the current
        FLAGS_dist_grad_bucket_bytes/trainable-set combination selects
        (empty before its first trace or with bucketing disabled):
        [{"bucket", "grads", "bytes", "keys"}] in reverse-backward
        issue order."""
        return list(self._step.current_bucket_plan())

    @staticmethod
    def _split(batch_and_labels, num_labels: int):
        if len(batch_and_labels) <= num_labels:
            raise ValueError(
                f"need at least {num_labels + 1} arrays (inputs + "
                f"{num_labels} labels), got {len(batch_and_labels)}")
        n = len(batch_and_labels) - num_labels
        ins = list(batch_and_labels[:n])
        lbls = list(batch_and_labels[n:]) if num_labels else []
        return ins, lbls

    def __call__(self, *batch_and_labels, num_labels: int = 1):
        # the entry layer's span: the whole call until it returns
        # (unblocked), `train.step.guard` / `.enqueue` inside it
        stats = self._step.stats
        with RecordEvent(
                "train.step",
                step=stats["captured_steps"] + stats["eager_steps"]) as span:
            ins, lbls = self._split(batch_and_labels, num_labels)
            loss = self._step.step(ins, lbls)
            span.set(compiled=int(loss is not None))
        return loss

    # -- checkpoint ---------------------------------------------------------
    def _tstates(self):
        """{param_name: slot dict} for every trainable param, creating
        (co-sharded) slots on demand — slot storage is the SHARED
        ``optimizer._states`` plane, so ``optimizer.state_dict()``
        round-trips cover captured distributed training too."""
        out = {}
        for k, p in self._params.items():
            if p.stop_gradient:
                continue
            out[k] = self._step._opt_state_for(p)
        return out

    def state_dict(self) -> Dict[str, Tensor]:
        """Optimizer-state slots as named Tensors for
        dist.save_state_dict (ref: the sharded-optimizer ckpt merge
        utilities in fleet; slot naming param.slot). Leaves are
        snapshot-copied: the live slot buffers are DONATED by the next
        captured step."""
        out = {}
        for k, slots in self._tstates().items():
            for name, v in slots.items():
                out[f"{k}#{name}"] = Tensor(jnp.copy(v))
        return out

    def set_state_dict(self, sd: Dict) -> None:
        states = self._tstates()
        unmatched = []
        covered = set()
        for key, t in sd.items():
            if "#" not in key:
                unmatched.append(key)
                continue
            pname, slot = key.rsplit("#", 1)
            if pname not in states:
                unmatched.append(key)
                continue
            covered.add((pname, slot))
            arr = t._data if isinstance(t, Tensor) else jnp.asarray(t)
            param_arr = self._params[pname]._data
            sharding = getattr(param_arr, "sharding", None)
            from jax.sharding import NamedSharding, PartitionSpec
            if isinstance(sharding, NamedSharding):
                if arr.shape != param_arr.shape:
                    # scalar slots (beta pows) replicate over the mesh
                    sharding = NamedSharding(sharding.mesh, PartitionSpec())
                if getattr(arr, "sharding", None) == sharding:
                    pass  # already placed (the dist-checkpoint load
                    # path fills slots with the param's own sharding);
                    # re-putting a multi-controller global array would
                    # be an unsupported cross-host transfer
                elif (isinstance(arr, jax.Array)
                      and not arr.is_fully_addressable):
                    raise ValueError(
                        f"optimizer slot {key!r} arrives as a "
                        f"multi-process array with sharding "
                        f"{arr.sharding} but the parameter needs "
                        f"{sharding}; reshard it via dist checkpoint "
                        f"load (host-side assembly) instead")
                else:
                    # a COMMITTED device array can't be device_put
                    # across processes (pinned src placement); hop
                    # through host — every process holds the full
                    # value, so the put only writes local shards
                    if isinstance(arr, jax.Array):
                        arr = np.asarray(arr)
                    arr = jax.device_put(arr, sharding)
            states[pname][slot] = arr
        missing = [f"{p}#{s}" for p, slots in states.items()
                   for s in slots if (p, s) not in covered]
        if unmatched or missing:
            raise ValueError(
                "optimizer checkpoint does not match the current model "
                "(resuming would silently reset state): "
                f"unmatched keys {unmatched[:5]}, "
                f"missing slots {missing[:5]}")

    # -- no-run diagnostics --------------------------------------------------
    def _abstract_opt_state(self):
        """Shape-only optimizer state (no device allocation): each
        slot's shapes/dtypes via eval_shape over the optimizer's own
        init fn — the trace-only probes must not materialize a second
        copy of the AdamW moments in exactly the memory-constrained
        configurations they diagnose. Ordered by the captured
        program's tkeys."""
        out = []
        for k in self._step._tkeys():
            p = self._params[k]
            out.append(jax.eval_shape(
                lambda d, _p=p: self.optimizer._init_state(
                    Tensor(d, stop_gradient=_p.stop_gradient)), p._data))
        return out

    def _probe_args(self, *batch_and_labels, num_labels: int = 1,
                    abstract: bool = False):
        """Shared arg prep for the no-run diagnostics (compile_stats /
        trace_jaxpr): current params/buffers/opt-state plus a FIXED
        probe rng key — a diagnostic must not advance the global RNG
        stream (seed-fixed training after a stats query stays
        identical). ``abstract=True`` substitutes ShapeDtypeStructs
        everywhere (trace-only callers: zero device allocation; note
        shardings are NOT carried, so compile-fidelity callers must use
        the concrete form)."""
        step = self._step

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        if abstract:
            # shape metadata only — np.asarray on host data reads shape/
            # dtype without any device transfer, honoring the
            # zero-device-allocation contract
            raw = [b._data if isinstance(b, Tensor) else b
                   for b in batch_and_labels]
            raw = [sds(r) if isinstance(r, jax.Array)
                   else sds(np.asarray(r)) for r in raw]
            params = {k: sds(t._data) for k, t in self._params.items()}
            buffers = {k: sds(t._data)
                       for k, t in self._swap.buffers.items()}
            states = self._abstract_opt_state()
            probe_rng = (jax.eval_shape(lambda: jax.random.key(0)),
                         jax.ShapeDtypeStruct((), jnp.uint32))
            lr = jax.ShapeDtypeStruct((), jnp.float32)
            return (params, buffers, states, lr, probe_rng, tuple(raw))
        ins, lbls = self._split(batch_and_labels, num_labels)
        raw = step._arrays(ins + lbls)
        params = {k: t._data for k, t in self._params.items()}
        buffers = {k: t._data for k, t in self._swap.buffers.items()}
        states = [dict(step._opt_state_for(self._params[k]))
                  for k in step._tkeys()]
        probe_rng = (jax.random.key(0), jnp.uint32(0))
        return (params, buffers, states, jnp.float32(0.0), probe_rng,
                tuple(raw))

    def compile_stats(self, *batch_and_labels, num_labels: int = 1,
                      return_compiled: bool = False):
        """Compile the step for these batch shapes WITHOUT running it and
        return XLA's memory analysis (argument/output/temp bytes). The
        auto-tuner's memory model prunes configs on this before paying
        for a trial run (ref: auto_tuner/prune.py's OOM-signature
        pruning, done here ahead of time from the compiled program).
        With return_compiled=True also returns the AOT executable so the
        caller can time steps without a second compile — call it as
        ``compiled(params, buffers, states, lr, rng, *arrays)``."""
        n_ins = len(batch_and_labels) - num_labels
        jitted = self._step._build("train", n_ins)
        args = self._probe_args(*batch_and_labels, num_labels=num_labels)
        params, buffers, states, lr, rng, raw = args
        compiled = jitted.lower(params, buffers, states, lr, rng,
                                *raw).compile()
        mem = compiled.memory_analysis()
        if return_compiled:
            return mem, compiled, (params, buffers, states, raw)
        return mem

    def trace_jaxpr(self, *batch_and_labels, num_labels: int = 1,
                    abstract: bool = False):
        """Trace (no compile) the step and return its ClosedJaxpr — the
        input to the static peak-memory estimator
        (auto_parallel.mem_estimator.estimate_peak_bytes).
        ``abstract=True`` traces from ShapeDtypeStructs: no device
        allocation at all (probe-safe in memory-tight configs)."""
        n_ins = len(batch_and_labels) - num_labels
        jitted = self._step._build("train", n_ins)
        args = self._probe_args(*batch_and_labels, num_labels=num_labels,
                                abstract=abstract)
        params, buffers, states, lr, rng, raw = args
        return jitted.trace(params, buffers, states, lr, rng,
                            *raw).jaxpr

    def _resync(self, params, buffers, states) -> None:
        """Rebind model/optimizer state after a caller drove the AOT
        executable directly (the auto-tuner trial loop): donation
        consumed the original buffers, so the threaded-through values
        become the live ones."""
        for k, t in self._params.items():
            t._data = params[k]
        for k, t in self._swap.buffers.items():
            t._data = buffers[k]
        opt = self.optimizer
        for k, ns in zip(self._step._tkeys(), states):
            opt._states[id(self._params[k])] = ns
