"""Runtime flag registry.

Mirrors the reference's exported-flag system (ref: paddle/common/flags.h:336-375,
flags_native.cc): flags are declared with a type + default, overridable from the
environment as ``FLAGS_<name>`` and at runtime via set_flags/get_flags
(ref: python/paddle/base/framework.py set_flags).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict

_BOOL_TRUE = {"1", "true", "yes", "on"}


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in _BOOL_TRUE


@dataclass
class _FlagInfo:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str
    value: Any = None


_registry: Dict[str, _FlagInfo] = {}


def _native_lib():
    """The native registry mirror is best-effort and LAZY: only mirror when
    the extension is already loaded, so `import paddle_tpu` never pays the
    g++ build (paddle_tpu._native compiles on ITS first import, triggered
    by the components that need it: store/profiler). _native/__init__
    back-fills flags defined before it loaded."""
    import sys
    mod = sys.modules.get("paddle_tpu._native")
    return getattr(mod, "lib", None)


def define_flag(name: str, default: Any, help: str = "") -> None:
    if isinstance(default, bool):
        parser: Callable[[str], Any] = _parse_bool
    elif isinstance(default, int):
        parser = int
    elif isinstance(default, float):
        parser = float
    else:
        parser = str
    info = _FlagInfo(name, default, parser, help, default)
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        info.value = parser(env)
    _registry[name] = info
    # mirror into the C++ registry (ref: flags_native.cc ExportedFlagInfoMap)
    # so native components observe the same flags
    lib = _native_lib()
    if lib is not None:
        lib.flag_define(name, str(info.value), help)


def get_flags(flags):
    """get_flags('FLAGS_x') or get_flags(['FLAGS_x', ...]) -> dict"""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f[len("FLAGS_"):] if f.startswith("FLAGS_") else f
        if key not in _registry:
            raise ValueError(f"Unknown flag {f}")
        out[f] = _registry[key].value
    return out


def set_flags(flags: Dict[str, Any]) -> None:
    for f, v in flags.items():
        key = f[len("FLAGS_"):] if f.startswith("FLAGS_") else f
        if key not in _registry:
            raise ValueError(f"Unknown flag {f}")
        info = _registry[key]
        info.value = info.parser(v) if isinstance(v, str) else v
        lib = _native_lib()
        if lib is not None:
            lib.flag_set(key, str(info.value))


def flag_value(name: str):
    return _registry[name].value


# Core flags (subset of the reference's ~180; ref: paddle/common/flags.cc)
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf in eager mode")
define_flag("pallas_autotune", False,
            "Measure Pallas block-size candidates at first use per shape "
            "and cache the winner (ref: kernels/autotune/cache.h)")
define_flag("check_nan_inf_stride", 1,
            "Ops between host fetches of the batched NaN-check flags. "
            "1 (default) = synchronous per-op raise, reference parity; "
            ">1 amortizes the host sync (one fetch per stride ops; "
            "essential over a high-RTT device link)")
define_flag("eager_delete_tensor_gb", 0.0, "GC threshold (no-op on TPU; XLA owns memory)")
define_flag("eager_fusion",
            _parse_bool(os.environ.get("PADDLE_TPU_EAGER_FUSION", "1")),
            "Lazy-eager elementwise fusion: defer fusable op chains and "
            "compile each chain as ONE jitted executable at the flush "
            "point (host read / non-fusable boundary / backward / chain "
            "cap). Kill switch: FLAGS_eager_fusion=0 or "
            "PADDLE_TPU_EAGER_FUSION=0 restores per-op dispatch")
define_flag("eager_fusion_reduce", True,
            "Reduction terminators in lazy-eager fusion: ops marked "
            "`fusable: reduce` (sum/mean/max/min/prod/logsumexp/...) "
            "join the deferred chain as terminator nodes (axis/keepdim "
            "in the cache key) instead of flushing it at dispatch. "
            "Granular kill switch under FLAGS_eager_fusion; 0 restores "
            "the flush-at-reduction boundary (flush reason "
            "reduce_boundary)")
define_flag("eager_fusion_epilogue", True,
            "Matmul/linear epilogue capture in lazy-eager fusion: ops "
            "marked `fusable: epilogue` defer as contraction nodes so a "
            "following bias-add/activation/cast chain compiles as the "
            "dot's XLA epilogue. Granular kill switch under "
            "FLAGS_eager_fusion; 0 keeps contractions on the per-op "
            "path (flush reason matmul_boundary)")
define_flag("eager_fusion_max_chain", 32,
            "Deferred-op count at which a fusion chain force-flushes; "
            "bounds compile time and the retained expression DAG")
define_flag("eager_fusion_cache", 256,
            "LRU capacity of the fusion program cache (entries keyed by "
            "DAG structure + input shapes/dtypes)")
define_flag("fused_optimizer", True,
            "One-executable optimizer step: flatten the whole parameter "
            "tree (params/grads/moments) and run grad unscale + finite "
            "check, global-norm clip and every per-param update as ONE "
            "jitted, buffer-donated executable (params and optimizer "
            "state update in place in HBM instead of allocating a second "
            "model copy). Per-step dynamic scalars (lr, loss scale) ride "
            "as 0-d device-array arguments so a changing LR schedule "
            "never recompiles. Kill switch: FLAGS_fused_optimizer=0 "
            "restores the per-param eager update loop")
define_flag("fused_optimizer_cache", 32,
            "LRU capacity of the fused optimizer-step program cache "
            "(entries keyed by optimizer type + parameter-tree structure "
            "+ dtypes/shapes + hyperparameter-static config)")
define_flag("fusion_flush_origin", False,
            "Attribute every fusion chain flush to its origin call "
            "site: fusion.flush_sites_total{reason, site} counts "
            "flushes per (reason, file:line), the planning input for "
            "whole-step capture (which code locations break capture, "
            "not just why). Off by default — the stack walk costs ~µs "
            "per flush; paddle_tpu.analysis audits record origins "
            "regardless of this flag")
define_flag("metrics", True,
            "Process-wide telemetry registry (paddle_tpu.observability): "
            "counters/gauges/histograms woven through dispatch, fusion, "
            "collectives, checkpointing and serving. Default ON. "
            "FLAGS_metrics=0 is the kill switch: every instrument "
            "mutation becomes one cached flag read + return")
define_flag("serving_block_size", 16,
            "Tokens per KV block in the paged serving cache "
            "(serving.PagedLlamaDecodeEngine): the block pool is "
            "[num_blocks, block_size, KVH, D] per layer and the tiled "
            "decode attention walks each slot's block table one block "
            "at a time. Larger blocks = fewer gather steps but coarser "
            "allocation granularity (internal fragmentation up to "
            "block_size-1 tokens per request)")
define_flag("serving_num_blocks", 0,
            "KV blocks in the paged serving pool, shared by all slots. "
            "0 (default) = auto-size to dense capacity parity "
            "(max_slots x ceil(max_seq/block_size)); smaller values "
            "trade worst-case capacity for HBM, relying on admission "
            "control (requests wait for blocks instead of OOMing)")
define_flag("serving_prefill_chunk", 64,
            "Max prompt tokens a single paged prefill executable "
            "processes: the GenerationServer loop interleaves one "
            "chunk with each decode step so a long prompt never "
            "stalls the in-flight decode batch for more than one "
            "chunk's forward pass")
define_flag("serving_spec_tokens", 4,
            "Draft tokens a speculative decode step proposes per "
            "target step (the speculation window). The target model "
            "verifies the whole window in ONE batched paged-attention "
            "call and commits the accepted prefix; greedy output is "
            "bit-equal to the non-speculative stream regardless of "
            "the window size — this only trades draft work against "
            "acceptance length")
define_flag("serving_spec_draft_layers", 0,
            "Decoder layers in the auto-built truncated-layer draft "
            "model (PagedLlamaDecodeEngine.make_draft): the draft "
            "shares the target's embedding/head/first-N-layer weights "
            "at zero extra weight HBM. 0 (default) = half the target's "
            "layers (min 1)")
define_flag("serving_admission_policy", "static",
            "Admission policy a GenerationServer builds when none is "
            "passed: 'static' keeps the FLAGS_serving_shed_queue rule "
            "(the fallback policy), 'adaptive' installs "
            "serving_supervisor.AdaptiveAdmissionPolicy — "
            "step-boundary EWMAs of blocks_free/backlog/throughput "
            "driving graceful brownout (speculative window, then "
            "prefill chunk) before hard shedding, plus deadline-aware "
            "rejection at submit")
define_flag("serving_supervisor_backoff", 0.05,
            "Base seconds of the ServingSupervisor's bounded "
            "exponential restart backoff: death N of a streak waits "
            "backoff * 2^(N-1), capped; the streak resets after a "
            "healthy stretch")
define_flag("serving_supervisor_stall_seconds", 0.0,
            "Decode-loop stall watchdog: a loop thread that is alive "
            "but has not heartbeat for this many seconds WHILE "
            "holding work is fenced and restarted like a crash (0 = "
            "stall detection off; an idle loop parked on the empty "
            "queue never counts as stalled)")
define_flag("serving_prefix_cache", True,
            "Content-addressed prefix sharing in the paged serving KV "
            "cache: committed prompt blocks enter a host-side radix "
            "tree keyed by their token ids, admission matches new "
            "prompts against it at block granularity, matched blocks "
            "are aliased into the slot's table with refcount bumps and "
            "their prefill is SKIPPED. Released prefixes stay cached "
            "(refcount 0) and are LRU-evicted under pool pressure. "
            "0 = kill switch: the allocator behaves byte-identically "
            "to the private-blocks-only design")
define_flag("serving_prefix_cache_blocks", 0,
            "Upper bound on KV blocks the prefix radix tree may hold "
            "(shared + cached); committing past the bound evicts "
            "refcount-0 LRU entries first and stops caching when "
            "nothing is evictable. 0 (default) = unbounded within the "
            "pool — the free-list/LRU pressure path is the only limit")
define_flag("serving_shed_queue", 0,
            "Load-shedding queue bound for the paged GenerationServer: "
            "when the KV block pool has no available blocks AND more "
            "than this many admitted-order requests are already "
            "deferred waiting for blocks, submit() rejects new work "
            "immediately (rejected reason=shed) instead of deferring "
            "unboundedly. 0 (default) disables shedding — exhaustion "
            "queues forever, the pre-policy behavior")
define_flag("serving_fleet_heartbeat_seconds", 0.5,
            "Fleet router heartbeat period: every replica's /health "
            "RPC is probed this often on a dedicated short-timeout "
            "connection, and the returned gauges (blocks_free, "
            "backlog, admission pressure level) feed KV-pressure-"
            "aware placement")
define_flag("serving_fleet_heartbeat_misses", 3,
            "Consecutive failed heartbeats before the fleet router "
            "declares a replica dead: its epoch is fenced (late "
            "responses discarded), in-flight requests fail over to "
            "healthy replicas seeded with their committed tokens, "
            "and resurrection begins. A data-plane connection error "
            "fences immediately without waiting for misses")
define_flag("serving_fleet_restart_backoff", 0.05,
            "Base seconds of the fleet router's bounded exponential "
            "resurrection backoff: relaunch attempt N of a dead "
            "replica waits backoff * 2^(N-1) (capped, full-jittered "
            "under FLAGS_backoff_full_jitter) before spawning the "
            "replacement process from the shared executable cache + "
            "warm bundle")
define_flag("serving_fleet_max_restarts", 8,
            "Resurrection attempts per dead replica before the fleet "
            "router gives up on it and degrades to the surviving "
            "replicas (the router itself never crashes; a degraded "
            "slot is journaled and counted)")
define_flag("serving_fleet_retry_after", 1.0,
            "Seconds clients are told to wait (the retry_after hint "
            "on the fleet-shed error) when every live replica reports "
            "admission pressure level 3 — fleet-level shed fires only "
            "after per-replica brownout has already been exhausted "
            "everywhere")
define_flag("use_bf16_matmul", True, "Prefer bfloat16 matmul accumulation defaults")
define_flag("log_level", 0, "Framework verbosity")
define_flag("benchmark", False, "Synchronize after each op for timing")
define_flag("retain_grad_for_all_tensor", False, "Keep .grad on non-leaf tensors")
