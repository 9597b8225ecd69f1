"""Checked-in lint allowlist: (rule, location-glob, one-line reason).

Contract (ISSUE 6): deliberate exceptions are encoded HERE, per rule
and per site/file, each with a justification — never by silencing a
rule globally. Patterns match the repo-relative file path
("paddle_tpu/ops/math.py"), the full location ("...py:121"), or the
diagnostic message ("FLAGS_log_level is registered*" — stable when
line numbers aren't); globs are fnmatch-style. Suppressed findings are still counted and listed by
``analysis.lint``/the CLI, so drift stays visible.

When you fix a site, delete its entry — tests/test_lint_clean.py keeps
the repo clean against the ACTIVE rule set, and a stale entry here is
dead weight the next reader has to reason about.
"""

ALLOWLIST = [
    # -- PTL001: deliberate device->host syncs ---------------------------
    ("PTL001", "paddle_tpu/core/tensor.py",
     "the host-interop API itself: __float__/__int__/__bool__ route "
     "through item() by definition"),
    ("PTL001", "paddle_tpu/__init__.py",
     "paddle.tolist() is the public host-conversion API"),
    ("PTL001", "paddle_tpu/ops/inplace.py",
     "Tensor.tolist fallback shim — host conversion is its contract"),
    ("PTL001", "paddle_tpu/ops/creation.py",
     "Tensor-valued fill/shape args must be host-static for XLA "
     "(shapes/fill enter the program as constants)"),
    ("PTL001", "paddle_tpu/ops/manipulation.py",
     "Tensor-valued axis/pad/section args must be host-static for XLA"),
    ("PTL001", "paddle_tpu/ops/math.py",
     "Tensor-valued clip bounds / top-k k must be host-static for XLA"),
    ("PTL001", "paddle_tpu/nn/functional/common.py",
     "Tensor-valued pad widths must be host-static for XLA"),
    ("PTL001", "paddle_tpu/nn/functional/vision.py",
     "Tensor-valued output shape must be host-static for XLA"),
    ("PTL001", "paddle_tpu/nn/functional/extension.py",
     "sequence lengths drive host-side loop bounds (pack/unpack)"),
    ("PTL001", "paddle_tpu/optimizer/lr.py",
     "ReduceOnPlateau branches scheduling on the metric value by "
     "contract (host decision)"),
    ("PTL001", "paddle_tpu/optimizer/extra.py",
     "LBFGS line search branches on the loss value by contract; the "
     "optimizer opts out of fusion (_fusable_step=False)"),
    ("PTL001", "paddle_tpu/hapi/model.py",
     "predict/summary host conversions by contract; the train/eval "
     "loss fetch is HOISTED to the fit/evaluate log boundary (lazy "
     "device loss, Fusion III) so the step hot path itself is "
     "sync-free"),
    ("PTL001", "paddle_tpu/hapi/callbacks.py",
     "VisualDL/metric logging is host-side by nature"),
    ("PTL001", "paddle_tpu/io/sampler.py",
     "numpy index arrays (host data already) — .tolist() here never "
     "touches the device"),
    ("PTL001", "paddle_tpu/audio/backends.py",
     "file-I/O backend: waveform data is host-resident by contract"),
    ("PTL001", "paddle_tpu/geometric/*",
     "graph sampling utilities run on host numpy by design"),
    ("PTL001", "paddle_tpu/incubate/*",
     "ASP mask search / graph-sample khop are host-side preprocessing"),
    ("PTL001", "paddle_tpu/vision/detection_ops.py",
     "NMS/bbox post-processing is host-side by design"),

    # -- PTL002: reference-parity flags, deliberately inert --------------
    # keyed on the flag name via message glob, not file:line — flags.py
    # gains a flag nearly every PR and a line pin would rot
    ("PTL002", "FLAGS_eager_delete_tensor_gb is registered*",
     "documented no-op on TPU (XLA owns memory); kept so reference "
     "set_flags() calls don't raise"),
    ("PTL002", "FLAGS_use_bf16_matmul is registered*",
     "accumulation policy is governed by JAX's "
     "default_matmul_precision on TPU; accepted-but-inert for "
     "reference parity"),
    ("PTL002", "FLAGS_log_level is registered*",
     "reserved verbosity surface (jit.set_verbosity is the live "
     "knob); accepted for reference parity"),

    # -- PTL003: deliberate lock-free mutations --------------------------
    ("PTL003", "paddle_tpu/core/autograd.py",
     "_pair_cache_strong.clear() is a GIL-atomic one-shot bound reset "
     "on the measured dispatch hot path; a lock would cost more than "
     "the benign worst case (a racing thread re-promotes its entry)"),
    ("PTL003", "paddle_tpu/core/fusion.py",
     "_pending_tensors pop at donation-site flush runs on the step "
     "thread; WeakValueDictionary ops are self-consistent under the "
     "GIL and a lost entry only re-flushes a chain"),
    ("PTL003", "paddle_tpu/core/random.py",
     "paired __enter__/__exit__ push/pop of the key-stream context "
     "stack; stream contexts are step-thread-confined by convention"),
    ("PTL003", "paddle_tpu/autograd/py_layer.py",
     "paired __enter__/__exit__ push/pop of the saved-tensor-hooks "
     "context stack; hook contexts are step-thread-confined"),
    ("PTL003", "paddle_tpu/jit/sot.py",
     "guard-digest memo eviction inside the (single-threaded) SOT "
     "trace replay; tracing two threads through one SOTFunction is "
     "unsupported upstream of this cache"),
    ("PTL003", "paddle_tpu/distributed/collective.py",
     "process-group teardown (destroy_process_group) is a collective "
     "lifecycle call — single-threaded by the bootstrap contract"),
    ("PTL003", "paddle_tpu/incubate/asp.py",
     "ASP mask registry mutates only in user-driven prune/reset calls "
     "(host-side preprocessing, not touched by worker threads)"),
]

# Capture-planner (PTC*) exceptions: classifications of the repo's OWN
# step functions (capture.scan_repo_steps, run in tier-1). Same
# contract as ALLOWLIST — (rule, glob, one-line WHY), stale entries
# fail tests — but kept separate because these suppress findings of
# the capture pass, not the linter, and each entry is a deliberate
# CAPTURE-BOUNDARY decision the Fusion III plan reads as
# "capture-compatible, by design".
CAPTURE_ALLOWLIST = [
    # (the historical hapi loss-fetch PTC003 entry is GONE: Fusion III
    # hoisted the fetch out of train_batch/eval_batch — they return a
    # lazy device loss and fit/evaluate fetch at the log boundary, so
    # the step functions now scan clean with no exception needed)
    # -- hot start (ISSUE 14): precise rows FIRST so the broad
    #    serving globs below don't absorb them with the wrong story --
    ("PTC002", "paddle_tpu/jit/sot.py*",
     "CapturedStep.prewarm is the BOOT-time AOT seam, not a step: it "
     "installs the warm bundle's rebuilt program into the LRU before "
     "the first step ever runs — the same program-cache bookkeeping "
     "_get_program does at compile time, never replayed state"),
    ("PTC002", "*`self.weight_swaps` inside the step*",
     "hot-swap bookkeeping advances exactly at the step boundary the "
     "swap is defined at: _apply_pending_swap runs between decode "
     "steps on the loop thread, installs a validated param tree, and "
     "never executes inside a captured program"),
    ("PTC002", "*`self._draft.*",
     "speculative decoding's draft mirror: the draft engine's slot "
     "state (last_ids/pos) is re-seeded from the TARGET's committed "
     "stream at the capture boundary — the draft/verify executables "
     "themselves are pure, only the accept/rollback bookkeeping "
     "between them mutates host state"),
    # -- self-healing serving plane (ISSUE 15): the supervisor/policy
    #    entry points are HOST control planes between captured
    #    programs — precise rows first, per concern ------------------
    ("PTC002", "*`self._steps_seen` inside the step*",
     "adaptive-admission evidence bookkeeping: on_step folds "
     "step-boundary gauges into host-side EWMAs and a step counter — "
     "the policy DECIDES between captured programs, it never executes "
     "inside one (brownout knobs only steer which already-compiled "
     "program the next iteration picks)"),
    ("PTC002", "paddle_tpu/serving_supervisor.py*",
     "crash-recovery/rollout host bookkeeping is the capture boundary "
     "BY DESIGN: strike/quarantine/restart counters and the "
     "re-admission of recovered requests (prompt + committed tokens "
     "through the normal prefill path) all advance while NO captured "
     "program is in flight — the dead loop is fenced first, the new "
     "loop replays the same pure compiled programs after"),
    # -- prefix-sharing KV (ISSUE 16): precise row first, same
    #    pattern as the hot-start/self-healing rows above ------------
    ("PTC002", "*`self.prefix_hit_tokens` inside the step*",
     "prefix-sharing admission bookkeeping: the radix-tree match, "
     "block aliasing and refcount bumps all run host-side in the "
     "allocator at admission — the capture boundary BY DESIGN; the "
     "captured prefill/decode programs see only the resulting block "
     "tables, and the one device-side effect (cloning the shared "
     "boundary block before its first write) is its own tiny jitted "
     "copy program (serving.prefix_cow), dispatched between steps"),
    # -- fleet serving fabric (ISSUE 17): the router is a pure HOST
    #    control plane across process boundaries — precise row so the
    #    broad serving glob below can't absorb it --------------------
    ("PTC002", "paddle_tpu/serving_fleet.py*",
     "fleet dispatch/fencing bookkeeping (the in-flight table, the "
     "epoch bump, failover/quarantine tallies) is the capture "
     "boundary BY DESIGN: the router never holds a tensor — replicas "
     "run the captured programs in their own processes, and every "
     "mutation here happens between RPC frames, with the zombie "
     "epoch's responses discarded rather than replayed"),
    ("PTC002", "paddle_tpu/serving.py*",
     "slot/block bookkeeping (pos/last_ids/active, block-table "
     "extension, prefill staging, speculative accept/rollback — "
     "committing the verified prefix and truncating rejected draft "
     "block writes) advances BETWEEN captured programs by design: "
     "the jitted _decode_impl, the _prefill_impl "
     "chunks and the spec propose/verify pair are the capture "
     "regions, the server loop is the boundary that replays them — "
     "pos where a launch is enqueued (step_enqueue/prefill_enqueue), "
     "last_ids where its tokens are fetched (step_collect/"
     "prefill_collect), one launch later"),
    ("PTC003", "paddle_tpu/serving.py*",
     "the per-step/per-window token fetch and the final-prefill-chunk "
     "first-token fetch ARE the decode contract: continuous batching "
     "must see each token on host to deliver it and to see an EOS "
     "(step_collect/prefill_collect, a launch behind the enqueue); "
     "decode_steps batches it to one fetch per window and a "
     "speculative step fetches ONCE for up to spec_k committed "
     "tokens (the verify outputs drive accept/rollback)"),
    ("PTC001", "paddle_tpu/amp/grad_scaler.py*",
     "the legacy override path ONLY: an optimizer with a custom "
     "step() (the LBFGS pattern) must run as written, so the found "
     "flag branches on host by contract — the plain path masks the "
     "update on device and never takes this branch, and under "
     "whole-step capture the entire scaler iteration (scale/backward/"
     "unscale/check/masked skip/scale bookkeeping) runs inside the "
     "ONE captured executable without reaching GradScaler.step at "
     "all"),
]
