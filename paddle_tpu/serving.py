"""Generation serving: fixed-slot continuous batching over compiled,
static-shaped step programs, with one decode engine.

``PagedLlamaDecodeEngine`` keeps the slots, the shared block pools a layer
names (``[num_blocks, block_size, row width]`` each) behind per-slot block tables
(``serving_cache``: ``PagedKVCache``, or ``KindedKVCache`` with a table a
kind of layer for a model with window layers), donation, warm-up bundles,
weight swaps and speculation. It holds no model's math.

**The seam.** ``model.serve_model()`` hands the engine a cache spec (a
layer's kind and the pools it owns, each named with its row width:
``{"k": KVH*D, "v": KVH*D}`` for per-head K and V, ``{"latent": 640}`` and
on some layers ``"index": 128`` beside it for a latent cache with a learned
indexer; every pool of a kind under that kind's one block table),
``build_params`` (its weights as the engine's pytree), ``embed``, ``head``
and one layer's step ``(h, the layer's pools, positions, the kind's block
table, carry) -> (h, pools, counts, carry)``; ``carry`` is whatever one
layer hands the layers after it within a launch (a selection of positions
that several layers share; None for a model with nothing to hand on). A
decode launch returns, beside its tokens, the positions that their rows
attended on each layer that made such a selection, as bitsets: they stay
on the device (``launch["selected"]``) for whoever checks the program.
The engine offers that step ``_write_rows`` (one row a token into each
named pool) and ``_write_kv`` (rope'd K/V rows, quantized for an int8
pool), ``_sc`` (``paged_attention``: ``_pa_kernel`` says whether the Pallas
kernel runs; ``paged_index_scores``, ``select_topk``,
``paged_latent_attention``), ``block_size``, ``n_rep``, and ``dtype`` /
``n_layers`` / ``int8`` for its parameters' layout. ``models/llama.py``,
``models/cohere2_moe.py``, ``models/glm_moe_dsa.py`` and
``models/solar_open2.py`` use it.

**A state a slot.** A layer's spec may name, beside or in place of
``pools``, ``state``: ``{name: (shape a slot, dtype)}``, kind ``"state"``
where it owns no pool. Its arrays are ``[max_slots, *shape]``, indexed by
SLOT and under no table or allocator (a recurrent layer's matrix state and
convolution tail); they ride in the same donated pytree as the pools and
reach the layer's step with them. Such a model's step also takes
``slots=`` (None: row ``s`` is slot ``s``, a decode step; ``[1]``: the slot
of a prompt chunk, whose program gets it as one more scalar). A chunk that
starts at position 0 reads the state as zeros; a row with ``wmask`` False
leaves it as it was. A state cannot be truncated, nor shared by prefix:
speculation, prefix sharing and an int8 pool are refused for such a model.

**Two program families**, through ``jit.sot.capture_jit``: ``serving.decode``
(``jit_serving_decode`` in a device trace), one token for every slot at its
own position, and ``serving.prefill_b<bucket>`` (``jit_serving_prefill_b8``
...), one prompt chunk of one slot padded to a power-of-two bucket up to
the chunk length. Speculation adds ``serving.spec_draft`` /
``serving.spec_verify``, prefix sharing ``serving.prefix_cow``.

**Chunking** is the engine's (``begin_request`` reserves blocks,
``prefill_chunk`` runs at most ``FLAGS_serving_prefill_chunk`` tokens a call);
**the loop** is ``GenerationServer._loop``: admit, enqueue one prompt chunk
and the decode launch for all active slots, then fetch and commit the launch
BEFORE it, sweep. The loop keeps one launch ahead of its fetch: a decode
iteration and a prompt chunk each come as two halves (``step_enqueue`` /
``step_collect``, ``prefill_enqueue`` / ``prefill_collect``), the tokens a
launch leaves on the device are the next launch's ``last_ids`` there
(``_next_ids``), and what can be known by counting (``pos``, ``max_new``,
the tables) is decided at enqueue. Only an EOS needs the token: it is seen
one launch late, and that launch's token for its slot is dropped.

**What an operator reads** (``GenerationServer.metrics_endpoint``:
``/metrics``), beside the load gauges and latency histograms:
``serving.failed_total`` and ``deadline_expired_total`` (requests lost, and
lost to the clock), ``weight_swaps_total`` / ``weight_swaps_rejected_total``
(whether a deploy landed), ``spec_accepted_total`` over
``spec_proposed_total`` (a draft's acceptance rate), ``prefix_hits_total``
and ``prefix_tokens_reused_total`` (the prefill the radix tree saves), and
``fetch_stalls_total{kind}`` (fetches that outlasted their launch by far,
each with a ``serving/fetch_stall`` record: ``_FetchWatch``)."""
from __future__ import annotations

import collections
import functools
import itertools
import math
import queue as _queue
import resource
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .observability import flight as _flight
from .observability import host as _host
from .observability import metrics as _om
from .profiler import RecordEvent as _span
from .utils import fault_injection as _fi

__all__ = ["PagedLlamaDecodeEngine", "GenerationServer"]

# process registry instruments (one set across all servers; the
# per-instance stats() dict stays the legacy view)
_M = _om.scope("serving")
_M_admitted = _M.counter("admitted_total", "Requests admitted into slots")
_M_rejected = _M.counter("rejected_total",
                         "Submissions rejected (server shutting down)")
_M_expired = _M.counter("deadline_expired_total",
                        "Requests failed by their deadline")
_M_failed = _M.counter("failed_total",
                       "Requests completed with an error")
_M_steps = _M.counter("steps_total", "Decode steps run by server loops")
_M_tokens = _M.counter("tokens_total", "Tokens delivered to requests")
_M_req_s = _M.histogram("request_seconds",
                        "Submit-to-completion wall time per request")
_M_token_s = _M.histogram(
    "token_seconds",
    "Per-token latency: request wall time / tokens produced")
_G_queue = _M.gauge("queue_depth",
                    "Requests waiting in the submission queue")
_G_inflight = _M.gauge("in_flight", "Requests currently holding a slot")
# queue-vs-decode latency split (the admission/load-shedding evidence:
# queue_seconds growing while decode_seconds holds means shed load)
_M_queue_s = _M.histogram(
    "queue_seconds", "Submit-to-admission wall time per request")
_M_decode_s = _M.histogram(
    "decode_seconds",
    "Admission-to-completion wall time per request (prefill + decode)")
# speculative decoding (per-step counted so acceptance rate is
# readable off the registry: accepted/proposed)
_M_spec_steps = _M.counter(
    "spec_steps_total", "Speculative decode steps (draft propose + "
    "one batched verify) run by engines")
_M_spec_proposed = _M.counter(
    "spec_proposed_total", "Draft tokens proposed to the target")
_M_spec_accepted = _M.counter(
    "spec_accepted_total",
    "Draft tokens the target verified and committed")
_M_spec_rolled = _M.counter(
    "spec_rolled_back_total",
    "KV blocks rolled back from rejected draft suffixes (re-credited "
    "to the slot's admission reservation)")
_M_shed = _M.counter(
    "shed_total",
    "Submissions rejected by the load-shedding policy (block pool "
    "exhausted AND the deferred-waiting list over "
    "FLAGS_serving_shed_queue, or the adaptive policy at its shed "
    "level)")
_M_deadline_rej = _M.counter(
    "admission_deadline_rejected_total",
    "Submissions rejected at submit time because the request's "
    "deadline cannot be met at the observed decode rate (adaptive "
    "admission; the request never burns KV blocks)")
# zero-downtime weight hot-swap (GenerationServer.swap_weights):
# applied between decode steps on the loop thread, in-flight requests
# keep their KV blocks and continue on the new weights
_M_swaps = _M.counter(
    "weight_swaps_total",
    "Weight hot-swaps applied by server loops (between decode steps; "
    "no request dropped, no recompile)")
_M_swap_rejected = _M.counter(
    "weight_swaps_rejected_total",
    "Weight hot-swaps rejected (shape/dtype/name mismatch against "
    "the live tree) — the old weights stay installed")
_M_swap_s = _M.histogram(
    "swap_seconds",
    "Wall seconds a weight hot-swap held the decode loop at its step "
    "boundary (weight prep + validation + install)")
# content-addressed prefix sharing (PagedKVCache radix tree):
# hits/reuse counted at TARGET admission only — an attached draft
# mirrors every admission, so counting both engines would double
# every hit (draft engines run with _prefix_metrics = False)
_M_prefix_hits = _M.counter(
    "prefix_hits_total",
    "Paged admissions whose prompt matched a cached prefix in the "
    "radix tree: matched blocks aliased with refcount bumps, their "
    "prefill skipped")
_M_prefix_reused = _M.counter(
    "prefix_tokens_reused_total",
    "Prompt tokens served from shared prefix blocks instead of "
    "being re-prefilled (the prefill work the radix cache saved)")

# the loop keeps one launch ahead of its fetch, so a request that ends
# on a token (an EOS) or on the clock (a deadline) has a token or two
# enqueued that nobody will read
# a layer whose memory is a state a slot starts a request from zeros: the
# request's first chunk reads no state (no clearing launch)
_M_state_resets = _M.counter(
    "state_resets_total",
    "First prompt chunks enqueued for a model with state layers: the "
    "slot's recurrent state and convolution tail are read as zeros")
_G_state_slots = _M.gauge(
    "state_slots_in_use",
    "Slots whose per-slot recurrent state a request holds (prefilling or "
    "decoding), for a model with state layers")
_G_state_bytes = _M.gauge(
    "state_bytes",
    "Bytes of the per-slot state arrays of every state layer, all slots")
_M_overrun = _M.counter(
    "overrun_tokens_total",
    "Tokens launched for a request and dropped because it had ended "
    "(EOS, deadline, failure) by the time they were fetched")
_M_stalls = _M.counter(
    "fetch_stalls_total",
    "Fetches that took over max(50 ms, 3 x the moving mean of their "
    "kind's fetches), by kind (decode/prefill); each left a "
    "serving/fetch_stall flight event")

# process-unique request trace ids: every lifecycle event of a request
# carries one, so a flight dump (or GenerationServer.trace) replays a
# single request's submit -> queued -> admitted -> decode -> terminal
# trail even across servers
_REQ_SEQ = itertools.count(1)

# 0-d int32 aval for pre-warm lowers: matches the jnp.int32(...) args
# the live host orchestration passes, without compiling anything
_I32 = jax.ShapeDtypeStruct((), np.int32)


def _feed_impl(last_ids, out, act):
    """``last_ids`` [S, 1] for the launch after one that is not fetched
    yet: the rows it stepped (``act``) read its tokens where they are,
    the head of ``out``; the others keep the host's."""
    return jnp.where(act[:, None], out[:last_ids.shape[0], None], last_ids)


def _feed_first_impl(last_ids, tok, slot):
    """``last_ids`` with ``slot``'s row set to the first token that its
    prompt's last chunk left on the device (the head of ``tok``)."""
    return jax.lax.dynamic_update_slice(
        last_ids, tok.reshape(-1)[:1, None], (slot, jnp.int32(0)))


@functools.lru_cache(maxsize=None)
def _feed_programs():
    """The two token-feedback programs (``jit_serving_feed*`` in a
    device trace), one pair for every engine of the process."""
    from .jit.sot import jit_named
    return (jit_named(_feed_impl, "serving.feed"),
            jit_named(_feed_first_impl, "serving.feed_first"))


_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
_metrics_on = _om.flag_info()


class _FetchWatch:
    """How long one engine's fetches take, and a record of each that
    outlasts its launch by far: a STALL, a fetch longer than
    ``max(STALL_FLOOR_S, STALL_OVER_MEAN x`` the moving mean of the
    fetches of its kind that were not). Off with ``FLAGS_metrics``. A
    fetch's span gets ``ready`` (1: the device had finished before the
    host arrived, so the whole fetch was host time). A stall sets
    ``stall=1`` on its span, adds 1 to ``serving.fetch_stalls_total{kind}``
    and writes the flight event ``serving/fetch_stall``: ``kind``, ``ms``,
    ``mean_ms``, ``ready``, ``since_enqueue_ms``, ``step`` (the decode
    launch's number; for a prompt's first token, that of the decode launch
    its chunk runs after), ``gc_ms`` / ``gc_full`` (collection time and
    generation-2 collections during the fetch, any thread), and over the
    fetch the loop thread's ``cpu_ms``, involuntary context switches
    (``invol_switches``) and ``major_faults``. The newest
    ``RECORDS_KEPT`` records stay in :meth:`stats`.

    Reading a record: ``gc_ms`` about ``ms``, the collector; ``ready`` 1
    and ``gc_ms`` about 0, other Python held the interpreter or the copy
    to the host was slow; ``ready`` 0, little ``cpu_ms`` and switches or
    faults that jump, the machine; otherwise the runtime did not run the
    launch."""

    STALL_FLOOR_S = 0.05
    STALL_OVER_MEAN = 3.0
    MEAN_WEIGHT = 0.05          # each fetch moves the mean a twentieth
    RECORDS_KEPT = 64

    def __init__(self):
        self.mean_s: Dict[str, float] = {}
        self.stalls = 0
        self.max_ms = 0.0
        self.records = collections.deque(maxlen=self.RECORDS_KEPT)

    @staticmethod
    def begin(handle, span) -> Optional[tuple]:
        """At the fetch's entry, inside its span: whether the device is
        done with ``handle``, and the marks a stall's record is taken
        against (None with metrics off)."""
        if not _metrics_on.value:
            return None
        ready = int(handle.is_ready())
        span.set(ready=ready)
        return (ready, time.perf_counter(), sum(_host.seconds),
                _host.counts[2], resource.getrusage(_RUSAGE_THREAD))

    def end(self, mark: Optional[tuple], span, kind: str,
            t_enqueue: float, step: int) -> None:
        """At the fetch's end, inside its span: a stall is recorded, any
        other fetch moves its kind's mean."""
        if mark is None:
            return
        ready, t0, gc0, full0, ru0 = mark
        now = time.perf_counter()
        took = now - t0
        mean = self.mean_s.get(kind)
        if mean is None or took <= max(self.STALL_FLOOR_S,
                                       self.STALL_OVER_MEAN * mean):
            self.mean_s[kind] = took if mean is None \
                else mean + self.MEAN_WEIGHT * (took - mean)
            return
        ru1 = resource.getrusage(_RUSAGE_THREAD)
        rec = {"kind": kind, "ms": 1e3 * took, "mean_ms": 1e3 * mean,
               "ready": ready, "since_enqueue_ms": 1e3 * (now - t_enqueue),
               "step": step, "gc_ms": 1e3 * (sum(_host.seconds) - gc0),
               "gc_full": _host.counts[2] - full0,
               "cpu_ms": 1e3 * (ru1.ru_utime + ru1.ru_stime
                                - ru0.ru_utime - ru0.ru_stime),
               "invol_switches": ru1.ru_nivcsw - ru0.ru_nivcsw,
               "major_faults": ru1.ru_majflt - ru0.ru_majflt}
        span.set(stall=1)
        _M_stalls.inc(kind=kind)
        _flight.record("serving", "fetch_stall", **rec)
        self.stalls += 1
        self.max_ms = max(self.max_ms, rec["ms"])
        self.records.append(rec)

    def stats(self) -> dict:
        return {"fetch_stalls": self.stalls,
                "fetch_stall_max_ms": round(self.max_ms, 3),
                "fetch_stall_records": list(self.records)}


class PagedLlamaDecodeEngine:
    """Paged-KV decode engine for a model that hands over the serving
    seam (``model.serve_model()``, module docstring).

    Layout: one shared pool per layer ``[num_blocks, block_size,
    KVH*D]`` (``serving_cache.PagedKVCache``) addressed through per-slot
    block tables, so KV HBM scales with ACTIVE tokens instead of
    slots x max_seq. A pool is allocated, written, copied and read in
    that one layout, the one the paged kernel's block copies read (a
    block is one contiguous ``[block_size, KVH*D]`` slab, tiled
    ``T(8,128)(2,1)`` on the v5e; a bf16 ``[..., KVH, 128]`` pool would
    be tiled ``T(4,128)(2,1)`` and copied whole before every attention
    call). Admission reserves a request's worst-case block
    count (prompt + generation budget), prompt blocks are mapped
    immediately, and decode extends one block at a time at step
    boundaries — extension can therefore never fail mid-stream.

    Prefill is CHUNKED: ``begin_request`` allocates, then
    ``prefill_chunk`` runs at most ``FLAGS_serving_prefill_chunk``
    prompt tokens through a bucketed executable per call, writing K/V
    straight into the slot's blocks; the GenerationServer loop
    interleaves one chunk with each decode step so a long prompt
    stalls the in-flight batch by at most one chunk forward.

    The decode step (``_decode_impl``, registered through
    ``capture_jit`` with the pool pytree donated) walks each slot's
    block list with the tiled streaming attention
    (``serving_cache.paged_attention``) — no ``[S, max_seq]`` score or
    cache view is ever materialized.

    ``kv_quant``: None stores blocks in the model dtype, "bfloat16"
    halves f32 pools, "int8" stores absmax codes + per-(token, head)
    scales (quantize.py math) dequantized per gathered tile.
    """

    # process-registry prefix metrics are target-engine only; an
    # attached draft mirrors every admission (attach_draft flips this)
    _prefix_metrics = True

    def __init__(self, model, max_slots: int = 4, max_seq: int = 256,
                 int8: bool = False, eos_id: Optional[int] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 num_layers: Optional[int] = None,
                 share_params: Optional[Dict[str, object]] = None,
                 prefix_cache: Optional[bool] = None):
        from .core.flags import flag_value
        self.block_size = int(block_size or
                              flag_value("serving_block_size"))
        mbs = -(-int(max_seq) // self.block_size)
        auto = int(max_slots) * mbs  # every slot at max_seq at once
        # one pool size, or one a kind of layer ({"full": n, "window": n})
        # for a model whose cache spec has window layers
        self.num_blocks = dict(num_blocks) if isinstance(num_blocks, dict) \
            else int(num_blocks or flag_value("serving_num_blocks") or auto)
        self._prefix_cache = prefix_cache
        if kv_quant not in (None, "bfloat16", "int8"):
            raise ValueError(
                f"kv_quant must be None, 'bfloat16' or 'int8', got "
                f"{kv_quant!r}")
        self.kv_quant = kv_quant
        self.prefill_chunk_len = int(
            prefill_chunk or flag_value("serving_prefill_chunk"))
        cfg = model.config
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.eos_id = eos_id
        self.int8 = bool(int8)
        # num_layers < cfg.num_hidden_layers builds the TRUNCATED-LAYER
        # view (first N decoder layers + the full norm/head): the cheap
        # draft model of speculative decoding shares every retained
        # weight with its target at zero extra HBM (see make_draft)
        self.n_layers = int(num_layers or cfg.num_hidden_layers)
        if not 1 <= self.n_layers <= cfg.num_hidden_layers:
            raise ValueError(
                f"num_layers must be in [1, {cfg.num_hidden_layers}], "
                f"got {num_layers}")
        # the seam: what this model asks of a cache and how its layer steps
        if not hasattr(model, "serve_model"):
            raise TypeError(
                f"{type(model).__name__} has no serve_model(): the engine "
                f"serves a model only through the seam it hands over "
                f"(cache_spec: a layer's block pools and/or its state a "
                f"slot; build_params, embed, layer, head, aux_names)")
        self._m = model.serve_model()
        if self.int8 and not self._m.supports_int8:
            raise NotImplementedError(
                f"int8 projections are not built for {type(model).__name__}")
        if not getattr(self._m, "supports_prefix_sharing", True):
            if self._prefix_cache:
                raise ValueError(
                    f"prefix sharing is not supported for "
                    f"{type(model).__name__}")
            self._prefix_cache = False
        self.cache_spec = self._m.cache_spec(self.n_layers)
        for sp in self.cache_spec:      # a state layer may name no pool
            sp.setdefault("pools", {})
            sp.setdefault("window", None)
        # layers whose memory is a fixed-size state a SLOT (`state`:
        # {name: (shape a slot, dtype)}), not a row a token: no table, no
        # blocks, nothing to truncate or to share by prefix
        self._state_names = list(dict.fromkeys(
            n for sp in self.cache_spec for n in sp.get("state", {})))
        self._stateful = bool(self._state_names)
        self._state_bytes = int(max_slots) * sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for sp in self.cache_spec
            for shape, dt in sp.get("state", {}).values())
        if self._stateful and kv_quant == "int8":
            raise NotImplementedError(
                "an int8 KV pool is not built for a model with state layers")
        # per-head K/V pools say their heads; a spec that does not (a
        # latent pool: one row a token, nothing per head) has no head
        # width for the paged kernel and no scale a head for int8
        sp0 = next((sp for sp in self.cache_spec if "kv_heads" in sp),
                   self.cache_spec[0])
        self.head_dim = sp0.get("head_dim")
        self.n_rep = sp0["q_heads"] // sp0["kv_heads"] \
            if "kv_heads" in sp0 else 1
        if self.head_dim is None and kv_quant == "int8":
            raise NotImplementedError(
                "an int8 KV pool keeps a scale a (token, head): not built "
                "for a cache spec without per-head pools")
        windows = {sp["window"] for sp in self.cache_spec if sp["window"]}
        if len(windows) > 1:
            raise NotImplementedError(
                f"window layers of several widths {sorted(windows)}")
        # the window of the model's window layers (None: every layer full)
        self.window = windows.pop() if windows else None
        # positions a row attends at most where the model selects them
        # (learned sparse attention; None: every visible position)
        self.select_k = getattr(self._m, "select_k", None)
        # layers whose rows score every live position of their slot through
        # the block table (a learned indexer's keys, pool `index`)
        self.index_layers = sum("index" in sp["pools"]
                                for sp in self.cache_spec)

        dt = jnp.bfloat16 if str(cfg.dtype) == "bfloat16" else jnp.float32
        self.dtype = dt

        if share_params is not None:
            # truncated-layer VIEW of another engine's params (the
            # make_draft path): re-bind the caller's device arrays —
            # never re-upload/re-transpose/re-quantize a second weight
            # set, which would transiently double weight HBM exactly
            # where speculative decoding wants headroom least
            p: Dict[str, object] = dict(share_params)
            p["layers"] = list(share_params["layers"])[:self.n_layers]
        else:
            p = self._build_params(
                {k: v._data for k, v in model.named_parameters()})
        self.params = p

        S = self.max_slots
        # host slot state
        self.pos = np.zeros(S, np.int32)          # next cache index
        self.active = np.zeros(S, bool)
        self.last_ids = np.zeros((S, 1), np.int32)

        from . import serving_cache as _sc
        self._sc = _sc
        # the implementation behind the paged_attention seam — Pallas
        # kernel vs jnp walk — is decided here ONCE, for every program the
        # engine builds (None where the cache has no per-head pool: that
        # seam is not run)
        self._pa_kernel = None if self.head_dim is None \
            else _sc.use_kernel_default(self.head_dim)
        self._draft: Optional["PagedLlamaDecodeEngine"] = None
        self._spec_k = 0
        # adaptive-admission brownout knobs, applied by the server at
        # step boundaries: _spec_suppressed drops speculative windows
        # to plain steps, _chunk_cap bounds the prefill chunk length
        # (both are step-boundary decisions — no compiled program
        # changes shape mid-stream)
        self._spec_suppressed = False
        self._chunk_cap: Optional[int] = None
        from .jit.sot import capture_jit as _capture_jit
        self._capture_jit = _capture_jit

        # (a state layer holds no blocks: it is under no table)
        kinds = sorted({sp["kind"] for sp in self.cache_spec
                        if sp["pools"] or not sp.get("state")})
        # a model with window layers gets a table and an allocator a
        # kind; every other model the one table it always had
        self._kinded = kinds not in ([], ["full"])
        if self._kinded:
            if self.kv_quant == "int8":
                raise NotImplementedError(
                    "an int8 KV pool is not built for window layers")
            given = self.num_blocks if isinstance(self.num_blocks, dict) \
                else {}
            # a kind with no size given holds what every slot may hold
            # at once: max_seq in a full table, window + chunk + a block
            # in a window table
            self._kv = _sc.KindedKVCache(
                self.max_slots, self.max_seq, self.block_size,
                {k: {"num_blocks": given.get(k),
                     "window": self.window if k == "window" else None,
                     "window_slack": self.prefill_chunk_len}
                 for k in kinds}, prefix_cache=self._prefix_cache)
            self.num_blocks = {k: c.num_blocks
                               for k, c in self._kv.kinds.items()}
        else:
            if isinstance(self.num_blocks, dict):
                self.num_blocks = int(self.num_blocks["full"])
            self._kv = _sc.PagedKVCache(
                max_slots=self.max_slots, max_seq=self.max_seq,
                block_size=self.block_size, num_blocks=self.num_blocks,
                prefix_cache=self._prefix_cache)
        self.kvs = self._alloc_pools()
        # layers that own a pool, by (kind, pool name): for the gauges
        self._pool_layers: Dict[tuple, int] = {}
        for sp in self.cache_spec:
            for name in sp["pools"]:
                key = (sp["kind"], name)
                self._pool_layers[key] = self._pool_layers.get(key, 0) + 1
        # counts a launch hands back (a model's `aux_names`) that no
        # fetch has read yet: a prompt chunk that is not its prompt's
        # last is never fetched, the next launch that is reads them
        self._aux_pending: List[object] = []
        self.last_aux: Dict[str, int] = {}
        # what is enqueued and not fetched (the serving loop keeps one
        # launch ahead of its fetch): the newest decode launch, whose
        # tokens the next launch reads on the device, and the first
        # token of each prompt whose last chunk no launch has read yet.
        # With neither, the host's last_ids is all there is
        self._ahead: Optional[dict] = None
        self._first_dev: Dict[int, object] = {}
        self._feed, self._feed_first = _feed_programs()
        # numbers the decode launches, and times the fetches: one far past
        # its kind's mean is a stall, and goes on record
        self._launch_seq = itertools.count(1)
        self._fetches = _FetchWatch()
        # how many times each slot has been activated: a launch keeps
        # the numbers it stepped, and its collect writes last_ids only
        # where the slot still holds that activation (a slot released
        # and re-activated while the launch was in flight is another
        # request's now)
        self._activation = np.zeros(self.max_slots, np.int64)
        # the pool pytree is donated each step/chunk: K/V writes land
        # in place in HBM. The jitted step is registered as a CAPTURED
        # step program (jit.sot.capture_jit): its clean capture plan is
        # checked in (tests/test_capture_plan.py), every call counts into
        # sot.captured_steps_total and the first compile lands in the
        # flight journal — identical execution to a bare jax.jit
        self._decode = self._capture_jit(self._decode_impl,
                                         donate_argnums=(1,),
                                         name="serving.decode",
                                         warm={"program": "decode",
                                               **self._warm_geo()})
        self._decode_collect = None
        self._prefills: Dict[int, object] = {}
        self._prefill_state: Dict[int, dict] = {}
        self.last_chunk: Dict[str, int] = {}
        # prefix-sharing state: the boundary copy-on-write program is
        # built lazily (first block-aligned hit), per-request hit
        # accounting feeds the server's req["prefix_hit_tokens"]
        self._cow = None
        self.prefix_hit_tokens: Dict[int, int] = {}

    def _build_params(self, sd) -> Dict[str, object]:
        """Device param pytree from a name -> array/Tensor state dict,
        as the model lays it out (``serve_model().build_params``): a
        swapped-in tree is layout-identical to a boot-time one and the
        compiled step programs are reused as-is."""
        return self._m.build_params(self, sd)

    @staticmethod
    def _leaf_specs(p) -> Dict[str, object]:
        """leaf name -> (shape, dtype) spec of a param pytree (int8
        (codes, scales) tuples spec both halves)."""
        def spec(v):
            if isinstance(v, tuple):
                return tuple(spec(x) for x in v)
            return (tuple(v.shape), str(v.dtype))

        out: Dict[str, object] = {}
        for k, v in p.items():
            if k == "layers":
                for i, lp in enumerate(v):
                    for nm, lv in lp.items():
                        out[f"layers.{i}.{nm}"] = spec(lv)
            else:
                out[k] = spec(v)
        return out

    def prepare_swap(self, state_dict):
        """Build the device param tree for a weight swap WITHOUT
        installing it — the expensive half (host->device upload,
        per-layer transposes, optional KV quantization) that a
        caller can run off the decode loop's thread; pass the result
        to ``swap_weights(prepared=...)`` for the cheap validate +
        pointer install at a step boundary."""
        return self._build_params(dict(state_dict))

    def swap_weights(self, state_dict=None, *, prepared=None) -> None:
        """Replace this engine's weights IN PLACE between decode
        steps: ``state_dict`` (model parameter names -> tensors, e.g.
        a ``CheckpointManager.restore`` payload) is prepped exactly
        like boot-time weights (or arrives pre-built via
        ``prepared=``, see :meth:`prepare_swap`), validated
        leaf-for-leaf against the live tree — same shapes/dtypes ⇒
        the compiled decode/prefill/spec programs are reused with
        ZERO recompiles — and only then installed. Any mismatch
        raises with the old weights intact.
        Slot state and KV blocks are untouched, so in-flight requests
        continue on the new weights with their history preserved. An
        attached weight-sharing draft (``make_draft`` view) is
        re-pointed at the new arrays in the same swap; an independent
        draft keeps its own weights (swap it separately) — the accept
        rule keeps the committed stream correct either way."""
        new_p = prepared if prepared is not None \
            else self._build_params(dict(state_dict))
        old_spec, new_spec = (self._leaf_specs(self.params),
                              self._leaf_specs(new_p))
        if old_spec != new_spec:
            bad = [k for k in sorted(set(old_spec) | set(new_spec))
                   if old_spec.get(k) != new_spec.get(k)]
            raise ValueError(
                f"weight swap rejected: {len(bad)} leaf(s) with "
                f"incompatible shape/dtype (first: {bad[:4]}) — a "
                f"zero-recompile swap requires the checkpoint to match "
                f"the serving model's geometry exactly")
        old = self.params
        self.params = new_p
        draft = self._draft
        if draft is not None and draft.params.get("emb") is \
                old.get("emb"):
            view: Dict[str, object] = dict(new_p)
            view["layers"] = list(new_p["layers"])[:draft.n_layers]
            draft.params = view

    def _warm_geo(self) -> Dict[str, object]:
        """The serving geometry recorded beside every warm-bundle
        program entry — what ``_bundle_stale`` checks a bundle's
        entries against at pre-warm time, so a bundle written by a
        differently-configured replica degrades to cold compile
        (counted ``warmup.failures_total{reason=stale}``) instead of
        silently replaying programs the persistent cache has no
        artifacts for."""
        return {"layout": "paged", "slots": self.max_slots,
                "max_seq": self.max_seq, "block_size": self.block_size,
                "num_blocks": self.num_blocks,
                "chunk": self.prefill_chunk_len}

    def _bundle_stale(self, meta, keys=None) -> List[str]:
        """Geometry keys on which a warm-bundle entry disagrees with
        this live engine (empty = fresh). ``keys`` restricts the
        check to the geometry a given program's SHAPE actually
        depends on — a replica differing only in an irrelevant knob
        (e.g. the prefill chunk, for a decode program) must not
        discard valid warmth. Keys absent from ``meta``
        (pre-freshness bundles) are not checked — the replay then
        simply rebuilds over live shapes as before."""
        geo = self._warm_geo()
        if keys is not None:
            geo = {k: geo[k] for k in keys if k in geo}
        return sorted(k for k, v in geo.items()
                      if k in meta and meta[k] != v)

    def _alloc_pools(self) -> Dict[str, list]:
        """Fresh zeroed cache arrays as the cache spec names them: ``{name:
        [one entry a layer]}``. A block pool's entry is ``[num_blocks of its
        kind, block_size, row width]`` where the layer's spec owns that pool
        (an int8 K/V pool also gets its scales); a state's entry is
        ``[max_slots, *shape a slot]`` in the dtype its spec gives, indexed
        by SLOT, under no table; None where the layer owns no such array.
        Built at boot and again at crash recovery (``reset_state``), where
        the donated pytree may be mid-donation."""
        pool_dt = {"int8": jnp.int8,
                   "bfloat16": jnp.bfloat16}.get(self.kv_quant,
                                                 self.dtype)
        bs = self.block_size
        blocks = [self.num_blocks[sp["kind"]] if self._kinded
                  else self.num_blocks for sp in self.cache_spec]
        names = list(dict.fromkeys(
            n for sp in self.cache_spec for n in sp["pools"]))
        # a row a token, the heads flat: what a block copy reads
        kv = {name: [jnp.zeros((nb, bs, sp["pools"][name]), pool_dt)
                     if name in sp["pools"] else None
                     for nb, sp in zip(blocks, self.cache_spec)]
              for name in names}
        if self.kv_quant == "int8":
            for name in ("ksc", "vsc"):     # a scale a (token, head)
                kv[name] = [jnp.zeros((nb, bs, sp["kv_heads"]), jnp.float32)
                            for nb, sp in zip(blocks, self.cache_spec)]
        for name in self._state_names:
            kv[name] = [
                jnp.zeros((self.max_slots,) + tuple(sp["state"][name][0]),
                          jnp.dtype(sp["state"][name][1]))
                if name in sp.get("state", {}) else None
                for sp in self.cache_spec]
        return kv

    def state_stats(self) -> Dict[str, int]:
        """Slots whose state a request holds now (prefilling or decoding)
        and the bytes of every layer's state arrays, all slots: ``{}`` for a
        model with no state layer."""
        if not self._stateful:
            return {}
        # (a slot that is prefilling is not active yet)
        return {"state_slots": self.max_slots,
                "state_slots_in_use": len(self._prefill_state)
                + int(self.active.sum()),
                "state_bytes": self._state_bytes}

    def host_stats(self) -> dict:
        """This engine's fetch stalls (``_FetchWatch``): their count, the
        longest in ms and the newest records."""
        return self._fetches.stats()

    def pool_blocks_in_use(self) -> Dict[str, int]:
        """Blocks mapped to slots by pool name, summed over the layers
        that own a pool of that name (a block of a table is one block in
        every pool under it). A state holds no blocks and is not here:
        ``state_stats`` counts its slots and bytes."""
        used = {k: c.used_blocks() for k, c in self._kv.kinds.items()} \
            if self._kinded else {"full": self._kv.used_blocks()}
        out: Dict[str, int] = {}
        for (kind, name), layers in self._pool_layers.items():
            out[name] = out.get(name, 0) + layers * used[kind]
        return out

    def reset_state(self) -> None:
        """Discard ALL slot and cache state — the crash-recovery seam:
        after a decode-loop crash the donated cache buffers may be
        mid-donation (deleted). Every owned slot is released as a
        counted EVICTION (its request is being re-admitted or
        quarantined by the supervisor), staged prefills are dropped, and
        the donated pytree (block pools, and a state layer's arrays a
        slot) is rebuilt as fresh zeros. Compiled
        programs are kept — zero recompiles. An attached draft resets
        in the same call (mirrored slots)."""
        for s in range(self.max_slots):
            self._kv.release(s, evicted=True)
        # the pool pytree is about to be rebuilt as ZEROS: every
        # cached radix node's block content dies with it, so the tree
        # must empty in the same breath (releasing all slots above
        # drove every refcount to 0 — reset cannot throw here)
        self._kv.reset_prefix_cache()
        self.prefix_hit_tokens.clear()
        self._prefill_state.clear()
        self._ahead = None
        self._first_dev.clear()
        self._aux_pending = []
        self.pos[:] = 0
        self.active[:] = False
        self.last_ids[:] = 0
        self.kvs = self._alloc_pools()
        if self._draft is not None:
            self._draft.reset_state()

    # -- device side --------------------------------------------------------
    def _write_rows(self, kvl, rows, positions, tables, wmask):
        """Scatter one row a token into each pool ``rows`` names (``{pool
        name: [S, T, row width]}``) at its (physical block, offset) cell;
        rows with ``wmask`` False or an unmapped table entry are dropped
        (OOB index), so prefill padding and inactive slots never touch a
        real block. Returns the layer's pools with those written."""
        return dict(kvl, **self._sc.write_rows(
            kvl, rows, positions, tables, wmask, self.block_size))

    def _write_kv(self, kvl, k, v, positions, tables, wmask):
        """Rope'd K/V rows [S, T, KVH, D] into their cells, each a row of
        KVH*D in the pool (``_write_rows``); an int8 pool takes codes and
        a scale a (token, head)."""
        S, T = positions.shape
        rows = {"k": k, "v": v}
        if self.kv_quant == "int8":
            # a scale a (token, head), taken before the heads are flat
            rows["k"], rows["ksc"] = self._sc.absmax_quantize(
                k.reshape((S * T,) + k.shape[2:]))
            rows["v"], rows["vsc"] = self._sc.absmax_quantize(
                v.reshape((S * T,) + v.shape[2:]))
        # (through the class: a caller may hand any object with `_sc` and
        # `block_size` as the engine)
        return PagedLlamaDecodeEngine._write_rows(
            self, kvl, {n: r.reshape(S, T, -1) for n, r in rows.items()},
            positions, tables, wmask)

    def _cow_impl(self, params, kvs, src, dst):
        """Boundary copy-on-write: clone physical block ``src`` into
        ``dst`` across every pool leaf (per-layer K/V + int8 scales).
        One captured executable with the pool pytree donated — the
        copy lands in place in HBM like every other pool write."""
        del params
        state = self._state_names
        return {name: pools if name in state     # indexed by slot: no block
                else [None if p is None
                      else self._sc.copy_block(p, src, dst) for p in pools]
                for name, pools in kvs.items()}

    def walk_group_tokens(self, T: int = 1) -> Optional[int]:
        """Tokens the paged-attention kernel fetches and computes on a
        loop step at this engine's shapes (``T`` rows a slot): a slot
        at ``pos`` walks ``pos + 1`` rounded up to it. None where the
        model's cache has no per-head pool for that kernel to walk."""
        if self.head_dim is None:       # no per-head pool: no such walk
            return None
        from .ops.pallas.paged_attention import group_tokens
        return group_tokens(
            self.block_size,
            self.cache_spec[0]["kv_heads"] * self.head_dim,
            self.kvs["k"][0].dtype, T, self.n_rep,
            self._kv.max_blocks_per_slot, self.kv_quant == "int8")

    def _forward_paged(self, params, kv, ids, positions, tables,
                       n_tiles, wmask, slots=None):
        """Shared chunked-prefill/decode body: ids [S, T] -> logits
        [S, T, V]; the pool pytree is donated, writes land in place.
        Also returns the model's counts summed over the layers, and
        ``{layer: what it made and handed on}`` for each layer that did
        not pass its ``carry`` on as it came (a selection ``[S, T, N]``
        bool that the layers after it share; ``{}`` for a model with
        none). ``slots`` goes to the layers of a model with state layers:
        None where row ``s`` of the batch is slot ``s`` (a decode step),
        else ``[S]`` int32, the slot of each row (a prompt chunk's one)."""
        h = self._m.embed(self, params, ids)
        # only a model with state layers is asked to take the slots
        more = {"slots": slots} if self._stateful else {}
        out_kv = {key: [] for key in kv}
        aux = carry = None
        made = {}
        for li, lp in enumerate(params["layers"]):
            kvl = {key: kv[key][li] for key in kv
                   if kv[key][li] is not None}
            # each layer reads the block table of its kind
            tab = tables[self.cache_spec[li]["kind"]] \
                if isinstance(tables, dict) else tables
            h, kvl, counts, handed = self._m.layer(
                self, li, lp, h, kvl, positions, tab, n_tiles, wmask, carry,
                **more)
            if handed is not carry:
                made[li] = carry = handed
            if counts is not None:       # summed over the layers
                aux = counts if aux is None else aux + counts
            for key in out_kv:
                out_kv[key].append(kvl.get(key))
        with jax.named_scope("paged.head"):
            logits = self._m.head(self, params, h)
            # barrier: without it XLA fuses the [H, V] head matmul into
            # the consumer argmax as a VPU reduce-loop fusion instead of
            # running the contraction on the MXU
            logits = jax.lax.optimization_barrier(logits)
        return logits, out_kv, aux, made

    @staticmethod
    def _with_aux(tok, aux):
        """The launch's token(s) with the model's counts behind them, so
        that the one fetch a launch has brings both."""
        if aux is None:
            return tok
        return jnp.concatenate([tok.reshape(-1), aux.astype(jnp.int32)])

    def _decode_impl(self, params, kv, last_ids, pos, tables, act):
        """One token for every slot: ids [S,1], pos [S] = write
        position, tables [S, max_blocks] block tables, act [S] bool
        (inactive slots neither write nor advance). ``n_tiles`` caps
        the block walk at the LONGEST history; behind the seam the
        jnp walk runs that far for every slot, the Pallas kernel stops
        each slot at its own last block. Beside the tokens and the
        pools, ``{layer: [S, words] uint32}``: the positions each row
        attended where a layer selected them, as bitsets (``{}`` for a
        model that selects nothing); they stay on the device unless
        somebody reads them."""
        positions = pos[:, None]                        # [S, 1]
        n_tiles = jnp.max(pos) // self.block_size + 1
        logits, kv, aux, made = self._forward_paged(
            params, kv, last_ids, positions, tables, n_tiles, act[:, None])
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        # barrier: the bitsets are packed after everything else. Without it
        # XLA:TPU plans the whole program's fast memory anew around them
        # (which weights it prefetches when, where the attention kernel's
        # operands live) and the launch came out 4 ms slower for 0.4 MB
        # more written (PERF.md, PR 33)
        made = jax.lax.optimization_barrier(made)
        return (self._with_aux(nxt, aux), kv,
                {li: self._sc.positions_bitset(sel[:, -1])
                 for li, sel in made.items()})

    def _prefill_impl(self, params, kv, ids, table_row, start, nvalid,
                      true_len, slot=None):
        """ONE prompt chunk for ONE slot: ids [1, B] (bucket-padded)
        holds prompt tokens [start, start+nvalid); rows write into the
        slot's blocks and attend to every earlier position (previous
        chunks' blocks + causal within the chunk). Returns the greedy
        token at the prompt's LAST position — meaningful only on the
        final chunk (the host ignores it before that). ``slot`` is given
        for a model with state layers alone: their state is indexed by
        slot, and a chunk whose ``start`` is 0 reads it as zeros."""
        B = ids.shape[1]
        offs = jnp.arange(B)
        positions = (start + offs)[None, :]             # [1, B]
        wmask = (offs < nvalid)[None, :]
        tables = jax.tree.map(lambda r: r[None, :], table_row)
        n_tiles = (start + nvalid - 1) // self.block_size + 1
        logits, kv, aux, _ = self._forward_paged(
            params, kv, ids, positions, tables, n_tiles, wmask,
            None if slot is None else slot[None])
        last = jnp.clip(true_len - 1 - start, 0, B - 1)
        tok = jnp.argmax(logits[0, last, :]).astype(jnp.int32)
        return self._with_aux(tok, aux), kv

    def _decode_collect_impl(self, params, kv, last_ids, pos, buf, i,
                             tables, act):
        """Decode step + on-device token collection (buf [S, n]
        donated; column i written in-place)."""
        nxt, kv, _ = self._decode_impl(params, kv, last_ids, pos, tables,
                                       act)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None],
                                           (jnp.int32(0), i))
        return nxt, kv, buf

    def _propose_impl(self, params, kv, last_ids, pos, tables, act):
        """DRAFT side of a speculative step: ``_spec_propose_k``
        sequential greedy decode steps chained device-side inside ONE
        captured executable (token feedback never touches the host),
        writing the draft's own block pool at positions
        [pos, pos + k). Returns (draft tokens [S, k], kv)."""
        ids, p = last_ids, pos
        toks = []
        for _ in range(self._spec_propose_k):
            nxt, kv, _ = self._decode_impl(params, kv, ids, p, tables,
                                           act)
            toks.append(nxt)
            ids = nxt[:, None]
            p = p + 1
        return jnp.stack(toks, axis=1), kv

    def _spec_verify_impl(self, params, kv, last_ids, draft_tok, pos,
                          tables, act):
        """TARGET side: score the whole speculation window in ONE
        batched paged-attention call — ids [S, k+1] = [last_id,
        d1..dk] at positions [pos, pos+k] (the same multi-position
        executable family chunked prefill runs), writing the target's
        K/V for every window position. Greedy targets t [S, k+1]
        (t[:, i] conditions on the prefix through d_i) and the
        device-computed accepted-prefix length n_acc [S] =
        |leading i with d_{i+1} == t_i| come back together; the host
        commits min(n_acc + 1, k) tokens and rolls the rest back, so
        the greedy stream is BIT-equal to non-speculative decode."""
        k = draft_tok.shape[1]
        ids = jnp.concatenate([last_ids, draft_tok], axis=1)
        positions = pos[:, None] + jnp.arange(k + 1)[None, :]
        n_tiles = (jnp.max(pos) + k) // self.block_size + 1
        wmask = jnp.broadcast_to(act[:, None], positions.shape)
        logits, kv, _, _ = self._forward_paged(params, kv, ids, positions,
                                               tables, n_tiles, wmask)
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        match = (draft_tok == t[:, :k]).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        return t, n_acc, kv

    # -- host orchestration -------------------------------------------------
    def _bucket(self, n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def make_draft(self, model,
                   num_layers: Optional[int] = None
                   ) -> "PagedLlamaDecodeEngine":
        """Build the cheap draft engine for speculative decoding as a
        TRUNCATED-LAYER view of this target: same geometry (slots,
        max_seq, block pool sizing, quantization), first
        ``num_layers`` decoder layers (default
        ``FLAGS_serving_spec_draft_layers``, 0 = half the target's,
        min 1) — and the retained weights are re-bound to the
        TARGET'S device arrays, so the draft costs only its own KV
        pool, never a second weight set."""
        from .core.flags import flag_value
        self._refuse_speculation()
        n = int(num_layers or flag_value("serving_spec_draft_layers")
                or max(1, self.n_layers // 2))
        if not 1 <= n <= self.n_layers:
            raise ValueError(
                f"draft num_layers must be in [1, {self.n_layers}] — "
                f"the TARGET's depth, not the model's — got {n} (a "
                f"draft at least as deep as its target makes "
                f"speculation strictly slower than plain stepping)")
        return PagedLlamaDecodeEngine(
            model, max_slots=self.max_slots, max_seq=self.max_seq,
            int8=self.int8, eos_id=self.eos_id,
            block_size=self.block_size, num_blocks=self.num_blocks,
            kv_quant=self.kv_quant,
            prefill_chunk=self.prefill_chunk_len, num_layers=n,
            share_params=self.params)

    def attach_draft(self, draft: "PagedLlamaDecodeEngine",
                     spec_tokens: Optional[int] = None
                     ) -> "PagedLlamaDecodeEngine":
        """Enable speculative decoding: ``draft`` (a make_draft view
        or ANY second engine over the same geometry) proposes
        ``spec_tokens`` (default ``FLAGS_serving_spec_tokens``) tokens
        per step; this target verifies the window in one batched
        call. Admission reserves ``spec_tokens`` extra budget per
        request so window pre-extension can never out-draw the
        reservation; rejected suffixes roll their blocks back
        (``PagedKVCache.truncate``). Returns self (chainable)."""
        from .core.flags import flag_value
        self._refuse_speculation()
        k = int(spec_tokens or flag_value("serving_spec_tokens"))
        if k < 1:
            raise ValueError(f"spec_tokens must be >= 1, got {k}")
        if (draft.max_slots != self.max_slots
                or draft.max_seq != self.max_seq
                or draft.block_size != self.block_size):
            raise ValueError(
                "draft engine geometry (max_slots/max_seq/block_size) "
                "must match the target's — the two advance in "
                "lockstep over mirrored slot state")
        if self.active.any() or self._prefill_state \
                or self._kv.occupied_slots():
            raise ValueError(
                "attach_draft requires an IDLE engine: requests "
                "admitted before attachment were reserved without the "
                "spec_k margin and have no mirrored draft slot, so "
                "the next step would exhaust mid-decode — exactly "
                "what admission reservations exist to prevent. Drain "
                "or release every slot first")
        self._draft = draft
        # every admission mirrors into the draft's pool/tree: counting
        # its prefix hits in the process registry would double every
        # hit (the draft keeps its own per-instance stats() view)
        draft._prefix_metrics = False
        self._spec_k = k
        draft._spec_propose_k = k
        self._spec_propose = draft._capture_jit(
            draft._propose_impl, donate_argnums=(1,),
            name="serving.spec_draft",
            warm={"program": "spec_draft", "k": k,
                  "draft_layers": draft.n_layers,
                  **self._warm_geo()})
        self._spec_verify = self._capture_jit(
            self._spec_verify_impl, donate_argnums=(1,),
            name="serving.spec_verify",
            warm={"program": "spec_verify", "k": k,
                  **self._warm_geo()})
        return self

    def _refuse_speculation(self) -> None:
        if not self._m.supports_speculation or self._kinded \
                or self._stateful:
            raise NotImplementedError(
                "speculative decoding is not built for this model: a "
                "rejected window would have to be rolled back out of a "
                "window layer's table, whose freed blocks are gone, or out "
                "of a state, which keeps no history")

    def _tables_dev(self, slot: Optional[int] = None):
        """The block table(s) as a launch takes them: the one array, or
        one a kind of layer; ``slot`` narrows to that slot's row."""
        # a table is rewritten in place between launches (a block
        # mapped for the next token, entries freed behind the window):
        # each launch gets a snapshot, since jnp.asarray may alias host
        # memory that a launch still in flight reads
        def dev(t):
            return jnp.asarray((t if slot is None else t[slot]).copy())
        bt = self._kv.block_tables
        if not self._kinded:
            return dev(bt)
        return {k: dev(t) for k, t in bt.items()}

    def _defer_aux(self, result) -> None:
        """Keep an unfetched launch's result (a prompt chunk that is not
        its prompt's last: nobody waits for it) for the next launch that
        is fetched to read its counts from."""
        if self._m.n_aux:
            self._aux_pending.append(result)

    def _unfetched_aux(self) -> List[object]:
        """Hand the launches nobody fetches, enqueued so far, to the one
        being enqueued: its fetch reads their counts, and waits for
        nothing that was enqueued after it."""
        taken, self._aux_pending = self._aux_pending, []
        return taken

    def _take_aux(self, fetched: np.ndarray, unfetched):
        """A fetched launch result's token(s), without the model's
        counts that ride behind them (``_with_aux``), and those counts:
        this launch's and the ``unfetched`` launches' before it
        (``aux_names`` summed, ``moe_launches`` how many launches they
        cover), which ``last_aux`` keeps too. ``{}`` for a model with
        none."""
        n = self._m.n_aux
        if not n:
            return fetched, {}
        tot = fetched[-n:].astype(np.int64)
        for pending in unfetched:
            tot = tot + np.asarray(pending)[-n:]
        self.last_aux = dict(zip(self._m.aux_names, (int(x) for x in tot)),
                             moe_launches=len(unfetched) + 1)
        return fetched[:-n], self.last_aux

    def _chunk_counts(self, start: int, tokens: int, bucket: int) -> dict:
        """What a prompt chunk's turn did, for the loop's span and
        flight event; with window layers, also the positions one of
        them reads for these rows; with selected attention, the (row,
        position) pairs a layer attends and the key blocks the index
        kernel copies."""
        out = {"start": start, "tokens": tokens, "bucket": bucket}
        if self.window is not None:
            out["window_tokens"] = start + tokens \
                - max(start - self.window + 1, 0)
        if self.select_k is not None:
            # sum over the rows of min(pos + 1, k)
            below = max(min(self.select_k - start, tokens), 0)
            out["selected_tokens"] = below * start \
                + below * (below + 1) // 2 + (tokens - below) * self.select_k
        if self.index_layers:
            # the key blocks the index kernel copies, its rows' slot read
            # through the table to the last row
            out["index_blocks"] = -(-(start + tokens) // self.block_size) \
                * self.index_layers
        if self._stateful:
            # the sub-chunks the model's chunk form takes for these rows
            out["state_subchunks"] = -(-tokens // self._m.state_subchunk)
        return out

    def _device_cow(self, slot: int, src: int, dst: int) -> None:
        """Run the boundary copy-on-write on device: block ``src`` ->
        ``dst`` in every pool leaf, remapped by the allocator before
        this call. Dispatched synchronously with admission/step
        bookkeeping, so program order guarantees the clone reads the
        shared content before any later pool write can touch it."""
        if self._cow is None:
            self._cow = self._capture_jit(
                self._cow_impl, donate_argnums=(1,),
                name="serving.prefix_cow",
                warm={"program": "prefix_cow", **self._warm_geo()})
        self.kvs = self._cow(self.params, self.kvs, jnp.int32(src),
                             jnp.int32(dst))
        if self._prefix_metrics:
            _flight.record("serving", "prefix_cow", slot=slot,
                           src=src, dst=dst)

    def _apply_cow(self, slot: int) -> None:
        """Consume the admission-recorded boundary COW (block-aligned
        full-prefix hit: the last matched block is cloned so the
        re-prefilled final prompt token writes privately)."""
        mv = self._kv.take_cow(slot)
        if mv is not None:
            self._device_cow(slot, *mv)

    def _shared_write_guard(self, slot: int) -> None:
        """Decode/spec writes land at ``pos >= len(prompt)``, past
        every shared block by construction (``commit_prefix`` only
        caches full PROMPT blocks) — but a write that DID land inside
        the shared prefix would corrupt every sharer's stream, so the
        boundary is guarded, not trusted: ``cow_for_write`` detaches
        the block (and raises loudly on a mid-prefix write) before
        the table ships to the device."""
        mv = self._kv.cow_for_write(slot, int(self.pos[slot]))
        if mv is not None:
            self._device_cow(slot, *mv)

    def begin_request(self, slot: int, prompt_ids,
                      max_new_tokens: int) -> bool:
        """Admit a request into ``slot``: map blocks for the prompt
        and reserve its worst-case generation budget (+ the
        speculation window when a draft is attached — verify writes
        up to ``spec_k`` positions past the committed stream before
        rollback). Returns False when the pool cannot cover it right
        now (caller should keep the request queued — exhaustion
        queues, never crashes); raises ValueError for a request the
        pool could NEVER hold. With a draft attached, the draft's
        pool admits the same request in lockstep."""
        prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = int(prompt_ids.shape[0])
        if not 0 < n <= self.max_seq - 1:
            raise ValueError(
                f"prompt length {n} not in [1, {self.max_seq - 1}]")
        budget = max(int(max_new_tokens), 1) + self._spec_k
        total = min(n + budget, self.max_seq)
        if not self._kv.admit(slot, n, total, token_ids=prompt_ids):
            return False
        if self._draft is not None:
            # both pools or neither: a draft that cannot cover the
            # mirror (defer OR a custom draft pool that could never
            # hold it) must not strand the target's blocks
            try:
                ok = self._draft.begin_request(slot, prompt_ids,
                                               budget)
            except Exception:
                self._kv.release(slot)
                raise
            if not ok:
                self._kv.release(slot)
                return False
        # prefix hit: matched tokens are already resident in aliased
        # blocks — prefill starts at the first unmatched token (a
        # block-aligned FULL match re-prefills only the last prompt
        # token, into its COW'd boundary clone, to seed the first
        # generated token)
        skip = self._kv.matched_tokens(slot)
        self._apply_cow(slot)
        self.prefix_hit_tokens[slot] = skip
        if skip and self._prefix_metrics:
            _M_prefix_hits.inc()
            _M_prefix_reused.inc(skip)
            _flight.record("serving", "prefix_hit", slot=slot,
                           tokens=skip, prompt=n)
        self._prefill_state[slot] = {"ids": prompt_ids, "next": skip}
        self.pos[slot] = 0
        self.active[slot] = False
        return True

    def _prefill_program(self, b: int):
        """The prefill executable of bucket ``b``, each under its own
        name (``jit_serving_prefill_b<b>`` in a device trace)."""
        if b not in self._prefills:
            self._prefills[b] = self._capture_jit(
                self._prefill_impl, donate_argnums=(1,),
                name=f"serving.prefill_b{b}",
                warm={"program": "prefill", "bucket": b,
                      **self._warm_geo()})
        return self._prefills[b]

    def prefill_enqueue(self, slot: int) -> Optional[dict]:
        """Enqueue the next prompt chunk for ``slot`` and wait for
        nothing. Returns None while prefill is incomplete. On the final
        chunk the slot is active from the next decode launch on, which
        reads the first generated token (greedy) where the chunk left
        it, on the device; what comes back is that token's handle for
        ``prefill_collect``."""
        st = self._prefill_state[slot]
        ids, start = st["ids"], st["next"]
        n = int(ids.shape[0])
        # _chunk_cap is the adaptive-admission brownout knob: under
        # pressure the policy bounds each chunk (floor 8 = the
        # smallest bucket) so prefill draws smaller slices of the
        # step budget; None = the configured chunk length
        limit = self.prefill_chunk_len if self._chunk_cap is None \
            else max(8, min(self.prefill_chunk_len, self._chunk_cap))
        c = min(limit, n - start)
        b = min(self._bucket(c), self.prefill_chunk_len)
        with _span("serving.prefill.enqueue"):
            padded = np.zeros((1, b), np.int32)
            padded[0, :c] = ids[start:start + c]
            if self._kinded:
                # window tables move on with the prompt: blocks behind
                # the chunk's first row's window go back, the chunk's
                # own are mapped (a full table's were at admission)
                self._kv.advance(slot, start, start + c - 1)
            row = self._tables_dev(slot)
            more = ()
            if self._stateful:
                more = (jnp.int32(slot),)
                if start == 0:      # the program reads the state as zeros
                    _M_state_resets.inc()
                    _flight.record("serving", "state_reset", slot=slot)
            tok, self.kvs = self._prefill_program(b)(
                self.params, self.kvs, jnp.asarray(padded), row,
                jnp.int32(start), jnp.int32(c), jnp.int32(n), *more)
        st["next"] = start + c
        # what this turn did, for the loop's span and flight event
        self.last_chunk = self._chunk_counts(start, c, b)
        # publish every fully-written prompt block into the radix
        # tree as soon as its last token lands: a concurrent
        # admission can hit a prefix whose OWNER is still prefilling
        # its tail (content-identical blocks dedupe against existing
        # nodes, remapping the table to the cached copy)
        self._kv.commit_prefix(slot, ids, st["next"])
        draft = self._draft
        if st["next"] < n:
            # draft prefill rides the same interleave budget: one
            # draft chunk per target chunk (same chunk length — a
            # make_draft view — finishes in lockstep; an arbitrary
            # second engine catches up on the final chunk below)
            if draft is not None and slot in draft._prefill_state:
                draft.prefill_enqueue(slot)
            self._defer_aux(tok)
            return None
        # a chunk is numbered by the decode launch it runs after
        first = {"slot": slot, "tok": tok, "aux": self._unfetched_aux(),
                 "t_enqueue": time.perf_counter(),
                 "step": (self._ahead or {}).get("step", 0)}
        del self._prefill_state[slot]
        self.pos[slot] = n
        self.active[slot] = True
        self._activation[slot] += 1
        self._first_dev[slot] = tok
        if draft is not None:
            while slot in draft._prefill_state:
                draft.prefill_enqueue(slot)
            # the draft's stream mirrors the TARGET's: its own
            # prefill token is discarded, the target's first token
            # seeds both engines' next step
            draft._first_dev.pop(slot, None)
        return first

    def prefill_collect(self, first: dict):
        """Fetch the first token that ``prefill_enqueue`` left on the
        device; returns it and the model's counts that its fetch brings
        (``_take_aux``). The host's ``last_ids`` takes it unless a
        decode launch has read it there already: that launch's collect
        writes what follows it."""
        slot = first["slot"]
        with _span("serving.prefill.fetch") as span:
            mark = self._fetches.begin(first["tok"], span)
            toks, counts = self._take_aux(np.asarray(first["tok"]),
                                          first["aux"])
            tok = int(toks.reshape(-1)[0])
            self._fetches.end(mark, span, "prefill", first["t_enqueue"],
                              first["step"])
        if self._first_dev.get(slot) is first["tok"]:
            del self._first_dev[slot]
            self.last_ids[slot, 0] = tok
            if self._draft is not None:
                self._draft.last_ids[slot, 0] = tok
        return tok, counts

    def prefill_chunk(self, slot: int) -> Optional[int]:
        """Run the next prompt chunk for ``slot``. Returns None while
        prefill is incomplete; on the final chunk, activates the slot
        and returns the first generated token (greedy): the two halves
        above, one after the other."""
        first = self.prefill_enqueue(slot)
        return None if first is None else self.prefill_collect(first)[0]

    def prefill(self, slot: int, prompt_ids,
                budget: Optional[int] = None) -> int:
        """One-shot prefill (tests / direct use): admits with
        ``budget`` generation tokens reserved (default: the worst case,
        max_seq - len(prompt)) and runs every chunk back to back. The
        server path uses begin_request + prefill_chunk instead to
        interleave with decode."""
        prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = int(prompt_ids.shape[0])
        if budget is None:
            budget = self.max_seq - n
        if not self.begin_request(slot, prompt_ids, budget):
            raise RuntimeError(
                f"KV block pool exhausted admitting slot {slot} "
                f"({self._kv.stats()}); release a slot or raise "
                f"FLAGS_serving_num_blocks")
        while True:
            first = self.prefill_chunk(slot)
            if first is not None:
                return first

    def _extend_tables(self) -> None:
        """Step-boundary block extension: map the block covering each
        active slot's next write position (drawn from its admission
        reservation, so this cannot fail)."""
        for s in range(self.max_slots):
            if self.active[s]:
                self._shared_write_guard(s)
                self._kv.ensure_token(s, int(self.pos[s]))

    def _next_ids(self):
        """``last_ids`` as the next decode launch reads them: the host's,
        and over them what only the device has yet: the tokens of the
        launch that is not fetched, and the first tokens of prompts
        whose last chunk no launch has read (each is read here once; the
        launch's own tokens carry it on from there)."""
        ids = jnp.asarray(self.last_ids.copy())
        if self._ahead is not None:
            ids = self._feed(ids, self._ahead["out"], self._ahead["act"])
        for slot in [s for s in self._first_dev if self.active[s]]:
            ids = self._feed_first(ids, self._first_dev.pop(slot),
                                   np.int32(slot))
        return ids

    def step_enqueue(self) -> dict:
        """The first half of a decode iteration: extend the tables,
        upload, and enqueue one token for ALL active slots; ``pos``
        advances here. Nothing is waited for: the launch's tokens stay
        on the device, where the next ``step_enqueue`` reads them, until
        ``step_collect`` fetches them. With a draft attached, the draft
        runs a mirrored (cheap, truncated-layer) step on the SAME
        inputs so its KV cache stays complete — a plain-step iteration
        (capacity fallback, direct use) must not punch holes in the
        draft's history, or every later speculation window would
        propose from garbage and acceptance would silently collapse."""
        draft = self._draft
        with _span("serving.decode.prepare"):
            self._extend_tables()
            act = jnp.asarray(self.active.copy())
            ids = self._next_ids()
            pos = jnp.asarray(self.pos.copy())
            tables = self._tables_dev()
        with _span("serving.decode.enqueue"):
            if draft is not None:
                for s in range(self.max_slots):
                    if self.active[s]:
                        draft._shared_write_guard(s)
                        draft._kv.ensure_token(s, int(self.pos[s]))
                _, draft.kvs, _ = draft._decode(
                    draft.params, draft.kvs, ids, pos,
                    draft._tables_dev(), act)
            nxt, self.kvs, selected = self._decode(
                self.params, self.kvs, ids, pos, tables, act)
        self.pos[self.active] += 1
        if draft is not None:
            draft.pos[self.active] = self.pos[self.active]
        # what the launch was enqueued for: its result, the slots it
        # steps, each in which of its activations, the unfetched
        # launches whose counts its fetch reads, and the positions its
        # rows attended where the model selects them (on the device; no
        # serving path fetches them)
        slots = np.flatnonzero(self.active)
        launch = {"out": nxt, "act": act, "slots": slots,
                  "activation": self._activation[slots],
                  "aux": self._unfetched_aux(), "selected": selected,
                  "t_enqueue": time.perf_counter(),
                  "step": next(self._launch_seq)}
        self._ahead = launch
        return launch

    def step_collect(self, launch: dict):
        """The second half: wait for ``launch``'s tokens; returns the
        next token per slot (garbage for the slots it did not step) and
        the model's counts that the fetch brings (``_take_aux``). The
        host's ``last_ids`` takes the tokens of the slots that are still
        in the batch for the activation the launch stepped: a slot
        released and taken again meanwhile holds another request's
        token, which this launch's must not overwrite."""
        with _span("serving.decode.fetch") as span:
            mark = self._fetches.begin(launch["out"], span)
            nxt, counts = self._take_aux(np.asarray(launch["out"]),
                                         launch["aux"])
            self._fetches.end(mark, span, "decode", launch["t_enqueue"],
                              launch["step"])
        if self._ahead is launch:
            self._ahead = None
        draft = self._draft
        for s, stepped in zip(launch["slots"], launch["activation"]):
            if self.active[s] and self._activation[s] == stepped:
                self.last_ids[s, 0] = nxt[s]
                if draft is not None:
                    draft.last_ids[s, 0] = nxt[s]
        return nxt, counts

    def step(self) -> np.ndarray:
        """One decode iteration for ALL active slots, enqueued and
        fetched; returns next token per slot (garbage for inactive
        slots — callers consult .active)."""
        return self.step_collect(self.step_enqueue())[0]

    def leave(self, slot: int) -> None:
        """Take ``slot`` out of the batch from the next launch on. It
        keeps its blocks and its reservation until ``release``: a launch
        in flight may still be writing them."""
        self.active[slot] = False

    def spec_ready(self) -> bool:
        """True when the next iteration can run speculatively: a
        draft is attached, at least one slot is active, and every
        active slot has room for the whole verify window (a slot
        within ``spec_k`` tokens of capacity drops the batch to plain
        single-token steps for that iteration — correctness never
        depends on the window fitting). A brownout
        (``_spec_suppressed``, set by the adaptive admission policy at
        a step boundary) also drops to plain steps: under block
        pressure the +spec_k window pre-extension is exactly the
        block draw to shed first."""
        if self._draft is None or self._spec_suppressed:
            return False
        act = [s for s in range(self.max_slots) if self.active[s]]
        if not act:
            return False
        k = self._spec_k
        return all(int(self.pos[s]) + k + 1 <= self.max_seq - 1
                   for s in act)

    def spec_step(self):
        """One SPECULATIVE decode iteration for all active slots: the
        draft proposes ``spec_k`` tokens (one captured executable,
        device-chained), the target verifies the whole window in one
        batched paged-attention call (a second captured executable),
        and ONE host fetch closes the window — the same fetch budget
        as a single plain step, for up to ``spec_k`` committed tokens.

        Greedy acceptance: with d1..dk the draft's proposals and
        t0..tk the target's greedy tokens per window position, the
        committed prefix is t[:m], m = min(|leading d_{i+1}==t_i|+1,
        k) — every committed token conditions on a committed prefix,
        so the stream is BIT-equal to non-speculative decode. The
        first rejection truncates ``pos`` and rolls the rejected
        suffix's block writes back through
        ``PagedKVCache.truncate`` (re-crediting the admission
        reservation); a fully-accepted window commits k tokens and
        leaves both engines exactly one pending write behind, the
        plain-step invariant.

        Returns ``(tokens [S, k+1], counts [S])``: row s's first
        ``counts[s]`` tokens are the committed stream continuation
        (garbage for inactive slots — callers consult ``.active``)."""
        k = self._spec_k
        draft = self._draft
        for s in range(self.max_slots):
            if self.active[s]:
                # window pre-extension, drawn from the +spec_k
                # admission margin: target writes [pos, pos+k],
                # draft writes [pos, pos+k-1]. Both engines COW-guard
                # the shared prefix first — a spec write must never
                # land in an aliased block (rollback would then rip
                # tokens out of every sharer's stream)
                self._shared_write_guard(s)
                draft._shared_write_guard(s)
                self._kv.reserve_through(s, int(self.pos[s]) + k)
                draft._kv.reserve_through(s, int(self.pos[s]) + k - 1)
        last = jnp.asarray(self.last_ids)
        pos = jnp.asarray(self.pos)
        act = jnp.asarray(self.active)
        draft_tok, draft.kvs = self._spec_propose(
            draft.params, draft.kvs, last, pos,
            jnp.asarray(draft._kv.block_tables), act)
        t, n_acc, self.kvs = self._spec_verify(
            self.params, self.kvs, last, draft_tok, pos,
            jnp.asarray(self._kv.block_tables), act)
        toks = np.asarray(t)
        acc = np.asarray(n_acc)
        counts = np.minimum(acc + 1, k).astype(np.int32)
        proposed = accepted = rolled = 0
        for s in range(self.max_slots):
            if not self.active[s]:
                continue
            m = int(counts[s])
            self.pos[s] += m
            self.last_ids[s, 0] = toks[s, m - 1]
            draft.pos[s] = self.pos[s]
            draft.last_ids[s, 0] = toks[s, m - 1]
            rolled += self._kv.truncate(s, int(self.pos[s]))
            rolled += draft._kv.truncate(s, int(self.pos[s]))
            proposed += k
            accepted += int(acc[s])
        _M_spec_steps.inc()
        if proposed:
            _M_spec_proposed.inc(proposed)
        if accepted:
            _M_spec_accepted.inc(accepted)
        if rolled:
            _M_spec_rolled.inc(rolled)
        return toks, counts

    def decode_steps(self, n: int) -> np.ndarray:
        """``n`` chained decode iterations with DEVICE-resident token
        feedback: dispatches pipeline asynchronously and ONE host fetch
        closes the window. Every slot must be active; returns [S, n]
        generated tokens. Blocks for the whole window are mapped up
        front so the device-side table stays valid without host
        round-trips."""
        if self._kinded or self._m.n_aux:
            raise NotImplementedError(
                "a device-resident decode window is not built for a "
                "model with window layers; use step()")
        if not self.active.all():
            raise ValueError(
                "decode_steps advances EVERY slot; use step() when "
                "some slots are free (the continuous-batching server "
                "path)")
        if int(self.pos.max()) + n > self.max_seq - 1:
            raise ValueError(
                f"decode_steps({n}) would write past the "
                f"{self.max_seq}-token capacity (max pos "
                f"{int(self.pos.max())})")
        for s in range(self.max_slots):
            self._shared_write_guard(s)
            self._kv.reserve_through(s, int(self.pos[s]) + n - 1)
        if self._decode_collect is None:
            self._decode_collect = self._capture_jit(
                self._decode_collect_impl, donate_argnums=(1, 4),
                name="serving.paged_decode_window")
        ids = jnp.asarray(self.last_ids)
        pos = jnp.asarray(self.pos)
        tables = jnp.asarray(self._kv.block_tables)
        act = jnp.asarray(self.active)
        buf = jnp.zeros((self.max_slots, n), jnp.int32)
        for i in range(n):
            nxt, self.kvs, buf = self._decode_collect(
                self.params, self.kvs, ids, pos, buf, jnp.int32(i),
                tables, act)
            ids = nxt[:, None]
            pos = pos + 1
        toks = np.asarray(buf)                      # the one fetch
        self.pos += n
        self.last_ids = toks[:, -1:].astype(np.int32).copy()
        return toks

    def generate(self, prompt_ids, max_new_tokens: int = 32,
                 slot: int = 0) -> List[int]:
        """Single-request convenience path (tests / warm-up): prefill,
        then greedy single-token steps until eos/budget/capacity. The
        admission reservation is sized to ``max_new_tokens`` so a
        short request holds only its own blocks."""
        out = [self.prefill(slot, prompt_ids, budget=max_new_tokens)]
        for _ in range(max_new_tokens - 1):
            if self.eos_id is not None and out[-1] == self.eos_id:
                break
            if self.pos[slot] >= self.max_seq - 1:
                break
            out.append(int(self.step()[slot]))
        self.release(slot)
        return out

    def release(self, slot: int, evicted: bool = False) -> None:
        """Free the slot AND return its blocks + reservation to the
        pool; ``evicted=True`` (expiry/failure/cancellation) counts
        them into ``serving.block_evictions_total``. An attached
        draft releases its mirrored slot in the same call."""
        self.active[slot] = False
        self.pos[slot] = 0
        self._prefill_state.pop(slot, None)
        self._first_dev.pop(slot, None)
        self.prefix_hit_tokens.pop(slot, None)
        self._kv.release(slot, evicted=evicted)
        if self._draft is not None:
            self._draft.release(slot, evicted=evicted)

    def _prewarm_entry(self, entry):
        """AOT-rebuild one recorded serving program (a warm-bundle
        entry) over this engine's live geometry: decode, prefill (per recorded
        bucket) and — with a draft attached — the speculative
        propose/verify pair, each rebuilt AOT over the live block-pool
        geometry (``lower().compile()`` = a persistent-cache disk
        read). Spec entries without a draft return False (skipped, not
        failed): the bundle writer's topology simply doesn't apply.
        Entries recorded against a DIFFERENT serving geometry
        (slots/blocks/chunk/spec_k — ``_bundle_stale``) return
        ``"stale"``: replaying them would compile fresh programs at
        boot while the counters claim warmth, so the caller counts
        ``warmup.failures_total{reason=stale}`` and boots cold
        instead."""
        meta = entry.get("meta") or {}
        prog = meta.get("program")
        if prog in ("spec_draft", "spec_verify") and self._draft is None:
            return False
        if prog in ("decode", "prefill", "spec_draft", "spec_verify"):
            # every paged program's shape depends on the POOL geometry;
            # the prefill chunk is NOT part of any program shape — it
            # only bounds which buckets are reachable, so a prefill
            # entry is stale exactly when its recorded bucket exceeds
            # the live chunk, and decode/spec entries ignore it
            stale = self._bundle_stale(
                meta, ("layout", "slots", "max_seq", "block_size",
                       "num_blocks"))
            if prog == "prefill" and isinstance(meta.get("bucket"),
                                                int) \
                    and meta["bucket"] > self.prefill_chunk_len:
                stale.append("bucket")
            if prog in ("spec_draft", "spec_verify") \
                    and "k" in meta and meta["k"] != self._spec_k:
                stale.append("k")
            if stale:
                _flight.record("warmup", "stale_entry",
                               program=str(prog),
                               mismatches=",".join(stale))
                return "stale"
        S = self.max_slots
        # NumPy-backed helper args (device_put, no compiled fill
        # programs): pre-warm must never compile anything the bundle's
        # writer didn't
        ids = jnp.asarray(np.zeros((S, 1), np.int32))
        pos = jnp.asarray(np.zeros(S, np.int32))
        tables = self._tables_dev()
        act = jnp.asarray(np.zeros(S, bool))
        if prog == "decode":
            self._decode._jitted.lower(
                self.params, self.kvs, ids, pos, tables, act).compile()
        elif prog == "prefill":
            b = int(meta.get("bucket", 0) or
                    min(self._bucket(1), self.prefill_chunk_len))
            # the same registration as prefill_chunk's, warm meta
            # included: a bundle RE-exported by this prewarmed replica
            # must carry the geometry too, or its entries would bypass
            # the freshness check downstream
            self._prefill_program(b)._jitted.lower(
                self.params, self.kvs,
                jnp.asarray(np.zeros((1, b), np.int32)),
                self._tables_dev(0),
                _I32, _I32, _I32,
                *((_I32,) if self._stateful else ())).compile()
        elif prog == "spec_draft":
            draft = self._draft
            if draft is None:
                return False
            self._spec_propose._jitted.lower(
                draft.params, draft.kvs, ids, pos,
                jnp.asarray(draft._kv.block_tables), act).compile()
        elif prog == "spec_verify":
            if self._draft is None:
                return False
            self._spec_verify._jitted.lower(
                self.params, self.kvs, ids,
                jnp.asarray(np.zeros((S, self._spec_k), np.int32)),
                pos, tables, act).compile()
        else:
            return False
        _flight.record("warmup", "serving_program", program=str(prog))
        return True

    def export_decode(self):
        """AOT-serialize the decode step via jax.export — the StableHLO
        artifact a serving process can run without this class (ref: the
        reference predictor's save/load of an analyzed program). The
        signature carries the block pools, per-slot block tables and
        the active mask; it returns what ``_decode_impl`` does: the
        tokens, the pools and the selecting layers' positions (``{}``
        for a model that selects none)."""
        avals = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (self.params, self.kvs, jnp.asarray(self.last_ids),
             jnp.asarray(self.pos), self._tables_dev(),
             jnp.asarray(self.active)))
        exported = jax.export.export(jax.jit(self._decode_impl))(*avals)
        return exported.serialize()


def _steps_in_halves(eng) -> bool:
    """True where the loop may keep a launch of ``eng`` in flight: it
    offers the two halves (``step_enqueue`` / ``step_collect``,
    ``prefill_enqueue`` / ``prefill_collect``, ``leave``). A duck-typed
    engine with only ``step()`` / ``prefill_chunk()`` is stepped through
    those, and nothing stays in flight."""
    return hasattr(eng, "step_enqueue")


class GenerationServer:
    """Iteration-level continuous batching around a
    :class:`PagedLlamaDecodeEngine`: requests are admitted into free
    slots at step boundaries, every step advances all active requests
    together, finished requests free their slot for the next admission
    — no request waits for another to finish (ref role: the
    multi-stream request loop of the reference's serving predictor).

    The loop splits prefill from decode: admission allocates + reserves
    KV blocks (pool exhaustion defers the request — it WAITS for blocks,
    it never crashes the loop), and each iteration advances at most
    ONE prompt chunk before the decode step, so a long prompt admitted
    mid-stream costs already-decoding requests one chunk forward per
    step instead of the whole prompt.

    The loop keeps ONE LAUNCH IN FLIGHT: a pass enqueues its prompt
    chunk and decode launch n+1 and only then fetches and commits
    launch n, so the device holds its next program when one ends and
    the host's work between programs runs beside a program. A launch
    carries which request each slot stepped for, and its tokens go only
    to requests that still hold their slot: one that ended meanwhile
    (an EOS, seen a launch late; a deadline) had its tokens in flight
    counted into ``serving.overrun_tokens_total`` where it ended. The
    loop lands everything first where it must not run ahead, and sees
    why from its own state: a weight swap pending, a speculative step
    (``spec_ready()``), no batch left (before it parks or leaves). An
    engine that offers only ``step()`` / ``prefill_chunk()`` steps and
    collects in one call, and nothing stays in flight.

    Robustness contract: ``submit(..., deadline=s)`` bounds a request's
    wall time — expiry (checked at step boundaries; queued, waiting
    for blocks, prefilling or active) fails THAT request with
    TimeoutError, keeping whatever tokens it already produced in
    ``req["out"]`` (and returning its KV blocks as counted evictions).
    ``shutdown()`` drains: new submissions are rejected immediately,
    in-flight and already-queued requests run to completion, then the
    loop exits — no completed token is ever dropped by a shutdown.

    Self-healing plane (``serving_supervisor``): admission routes
    through a policy object (``policy=`` /
    ``FLAGS_serving_admission_policy``) consulted at submit and fed
    evidence at step boundaries, and the loop exports the supervision
    seams — a heartbeat (``_beat``/``_idle``), an epoch fence (a
    restarted loop's zombie predecessor exits without touching
    state), and a BaseException boundary that journals the crash and
    refreshes the gauges before the thread dies — so
    ``serving_supervisor.supervise(server)`` can restart a crashed or
    stalled loop and resume its in-flight streams bit-equal from
    their committed tokens."""

    _STOP = object()  # queue sentinel: wake the loop for shutdown

    def __init__(self, engine: PagedLlamaDecodeEngine, policy=None):
        self.engine = engine
        self._q: "_queue.Queue" = _queue.Queue()
        self._slots: Dict[int, dict] = {}
        # admission is split from activation: a slot in
        # _prefilling holds blocks and runs one prompt chunk per loop
        # iteration; _waiting holds admitted-order requests deferred
        # because the block pool couldn't cover their reservation yet
        # (the supervisor also re-admits recovered requests through
        # its head, so they precede anything newer)
        self._prefilling: Dict[int, dict] = {}
        self._waiting: List[dict] = []
        self._cancel_waiting = False  # set by shutdown(drain=False)
        self.steps_run = 0
        self.launched_ahead = 0     # steps enqueued before the last's fetch
        self._pool_gauged = None    # the pools' block counts last gauged
        self._state_gauged = None   # and a state model's slots and bytes
        # a model's counts that the collects returned, until a launch's
        # span takes them
        self._aux_carry: Dict[str, int] = {}
        self.admitted = 0
        self.rejected = 0           # submissions after shutdown/shed
        self.shed = 0               # rejections by load-shedding alone
        self.deadline_rejected = 0  # unmeetable-deadline rejections
        self.deadline_expired = 0   # requests failed by their deadline
        self.weight_swaps = 0       # hot-swaps applied by this loop
        self.tokens_delivered = 0   # committed tokens (policy evidence)
        self.loop_restarts = 0      # supervisor restarts of this loop
        self.recovered = 0          # requests resumed after a crash
        self.quarantined = 0        # poison requests failed, not retried
        # admission policy: a ServingSupervisor-plane object consulted
        # at submit time (admit_verdict) and fed evidence at step
        # boundaries (on_step). Default (None) follows
        # FLAGS_serving_admission_policy — "static" keeps the
        # FLAGS_serving_shed_queue behavior as the fallback policy
        if policy is None:
            from .serving_supervisor import default_policy
            policy = default_policy()
        self.policy = policy
        self._stopping = threading.Event()
        self._drained = threading.Event()
        # orders submit's stopping-check+enqueue against shutdown's
        # stopping.set(): a request that passed the check is enqueued
        # BEFORE stopping becomes visible, so the drain loop (which
        # only exits on stopping AND empty queue) cannot strand it
        from .analysis.locks import make_lock
        self._submit_lock = make_lock("serving.submit")
        # pending weight hot-swap: (state_dict, done Event, result
        # slot), set under the submit lock, applied by the LOOP thread
        # at its next step boundary (never mid-decode)
        self._swap_req = None
        self._metrics_server = None
        # supervision plane: _epoch fences zombie loop threads (a
        # stalled thread that wakes after a supervisor restart sees a
        # newer epoch and exits without touching state), _beat is the
        # loop heartbeat the stall watchdog reads, _idle marks the
        # loop parked on the empty queue (not a stall)
        self._epoch = 0
        self._beat = time.monotonic()
        self._idle = False
        self._start_loop()

    def _start_loop(self) -> None:
        """Start (or, from the supervisor, RESTART) the decode-loop
        thread. The crashed/crash-error markers reset so the
        supervisor can tell this incarnation's death from the last
        one's, and the heartbeat restarts NOW — a restarted loop must
        not inherit the dead one's stale beat, or the stall watchdog
        would re-fire before the new thread's first iteration."""
        self._crashed = False
        self._crash_error: Optional[BaseException] = None
        self._beat = time.monotonic()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serving-loop")
        self._thread.start()

    def _fenced(self) -> bool:
        """True on a ZOMBIE loop thread: one whose stamped epoch (set
        at its loop entry) no longer matches the server's. Mutation
        paths the loop calls into (_admit_paged/_run_prefill) check
        this before touching request dicts or the slot tables, so a
        stalled thread that wakes mid-recovery cannot double-commit
        tokens or register stale slots beside the replacement loop.
        Non-loop threads (tests driving admit helpers directly) carry
        no stamp and are never fenced."""
        my = getattr(threading.current_thread(),
                     "_serving_loop_epoch", None)
        return my is not None and my != self._epoch

    def _run(self) -> None:
        """Decode-loop thread body: the loop, plus the BaseException
        boundary the satellite audit asked for — a KillPoint (or any
        other escape ``except Exception`` must not swallow) still
        kills this thread, but first the crash is journaled and the
        gauges refreshed so ``queue_depth``/``in_flight`` read the
        TRUE post-crash state (requests still holding slots/blocks)
        instead of whatever the last completed step boundary wrote.
        The re-raise keeps ``threading.excepthook`` crash forensics
        (automatic flight dump) intact."""
        try:
            self._loop()
        except BaseException as e:
            self._crashed = True
            self._crash_error = e
            _flight.record("serving", "loop_crashed",
                           error=type(e).__name__,
                           in_flight=len(self._slots)
                           + len(self._prefilling))
            self._set_gauges()
            raise

    def _apply_brownout(self, spec_off: bool,
                        chunk_cap: Optional[int]) -> None:
        """Install the adaptive policy's brownout knobs on the engine
        (step-boundary-safe: both only steer which ALREADY-COMPILED
        program the next iteration picks)."""
        eng = self.engine
        eng._spec_suppressed = bool(spec_off)
        eng._chunk_cap = chunk_cap

    def metrics_endpoint(self, port: int = 0, host: str = "127.0.0.1"):
        """Serve the process metrics registry over HTTP: ``GET /metrics``
        (Prometheus text exposition) + ``/metrics.json`` (the nested
        snapshot) + ``/healthz`` (readiness: decode loop alive,
        supervisor not given up, admission pressure — the same snapshot
        the fleet router's probe reads). Idempotent per server; the
        endpoint is closed by ``shutdown()``. Returns the handle
        (``.url``, ``.port``, ``.close()``)."""
        if self._metrics_server is None:
            from .observability.http import start_metrics_server
            from .serving_fleet import health_snapshot
            self._metrics_server = start_metrics_server(
                port=port, host=host,
                health_cb=lambda: health_snapshot(self))
        return self._metrics_server

    def submit(self, prompt_ids, max_new_tokens: int = 32,
               deadline: Optional[float] = None) -> dict:
        """Enqueue a request. ``deadline`` (seconds from now) bounds its
        total wall time; None = unbounded. The returned dict carries
        ``trace_id`` — the key of this request's flight-recorder
        lifecycle trail (see :meth:`trace`)."""
        trace_id = f"req-{next(_REQ_SEQ)}"
        _flight.record("serving", "submit", trace_id=trace_id,
                       max_new=int(max_new_tokens))
        if self._stopping.is_set():
            self.rejected += 1
            _M_rejected.inc()
            _flight.record("serving", "rejected", trace_id=trace_id,
                           reason="shutting_down")
            raise RuntimeError(
                "GenerationServer is shutting down; new submissions are "
                "rejected (in-flight requests are draining)")
        if int(max_new_tokens) < 1:
            _flight.record("serving", "rejected", trace_id=trace_id,
                           reason="invalid_max_new")
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens} "
                f"(prefill always produces the first token)")
        if deadline is not None and deadline <= 0:
            _flight.record("serving", "rejected", trace_id=trace_id,
                           reason="invalid_deadline")
            raise ValueError(f"deadline must be > 0, got {deadline}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        # the admission policy decides here, on submit's thread, from
        # evidence the loop refreshed at its last step boundary:
        # "shed" (hard overload) or "deadline" (the request could not
        # finish in time at the observed rate — rejecting NOW spares
        # its blocks AND the caller's wait)
        verdict = self.policy.admit_verdict(
            self, int(prompt.shape[0]), int(max_new_tokens), deadline)
        if verdict is not None:
            self.rejected += 1
            _M_rejected.inc()
            if verdict == "deadline":
                self.deadline_rejected += 1
                _M_deadline_rej.inc()
            else:
                self.shed += 1
                _M_shed.inc()
            _flight.record("serving", "rejected", trace_id=trace_id,
                           reason=verdict,
                           policy=self.policy.name,
                           waiting=len(self._waiting))
            raise RuntimeError(
                f"request rejected by the {self.policy.name} admission "
                f"policy (reason={verdict}): "
                + ("its deadline cannot be met at the observed decode "
                   "rate — retry with a larger deadline or fewer "
                   "tokens" if verdict == "deadline" else
                   "the replica is overloaded (KV blocks exhausted "
                   "with a deferred backlog) — retry later or raise "
                   "FLAGS_serving_num_blocks"))
        # `launched` counts the tokens enqueued for the request, `out`
        # holds the ones fetched and delivered
        req = {"prompt": prompt,
               "max_new": int(max_new_tokens), "out": [], "launched": 0,
               "done": threading.Event(), "error": None,
               "trace_id": trace_id,
               "t0": time.monotonic(),
               "expires": (time.monotonic() + deadline
                           if deadline is not None else None)}
        with self._submit_lock:
            if self._stopping.is_set():
                self.rejected += 1
                _M_rejected.inc()
                _flight.record("serving", "rejected", trace_id=trace_id,
                               reason="shutting_down")
                raise RuntimeError(
                    "GenerationServer is shutting down; new submissions "
                    "are rejected (in-flight requests are draining)")
            self._q.put(req)
        _flight.record("serving", "queued", trace_id=trace_id,
                       prompt_len=int(req["prompt"].shape[0]))
        return req

    def generate(self, prompt_ids, max_new_tokens: int = 32,
                 timeout: float = 300.0,
                 deadline: Optional[float] = None) -> List[int]:
        req = self.submit(prompt_ids, max_new_tokens, deadline=deadline)
        if not req["done"].wait(timeout):
            raise TimeoutError("generation timed out")
        if req["error"] is not None:
            raise req["error"]
        return list(req["out"])

    @staticmethod
    def _swap_state(source) -> dict:
        """Normalize a swap source into a model state dict ON THE
        CALLER'S THREAD (disk reads and CRC verification never stall
        the decode loop): a ``CheckpointManager`` restores its newest
        good checkpoint, a path loads through the verifying
        ``framework.checkpoint`` reader, a dict passes through —
        with the conventional 'model'/'state_dict' sub-tree peeled
        off by ``extract_state_dict``."""
        from .framework.checkpoint import (CheckpointManager,
                                           extract_state_dict,
                                           load_checkpoint)
        if isinstance(source, CheckpointManager):
            got = source.restore()
            if got is None:
                raise ValueError(
                    f"no loadable checkpoint under {source.root!r} to "
                    f"swap from")
            source = got[1]
        elif isinstance(source, str):
            source = load_checkpoint(source)
        return extract_state_dict(source)

    def swap_weights(self, checkpoint_or_state=None,
                     timeout: Optional[float] = 300.0, *,
                     prepared=None) -> dict:
        """Zero-downtime weight hot-swap: install new weights into the
        running engine BETWEEN decode steps, without dropping or
        corrupting any in-flight request — their KV blocks and partial
        streams are untouched and the next decode step runs on the new
        weights (an attached weight-sharing draft rolls in the same
        swap).

        ``checkpoint_or_state``: a model state dict, a checkpoint path
        (verified by the ``framework.checkpoint`` reader), or a
        ``CheckpointManager`` (its newest good checkpoint). Weight
        prep (disk I/O + the full host->device build,
        :meth:`~PagedLlamaDecodeEngine.prepare_swap`) happens on THIS
        thread; the loop thread only validates + pointer-installs at
        its next step boundary. Same shapes/dtypes ⇒ zero recompiles;
        any mismatch raises here with the old weights intact (counted
        in ``serving.weight_swaps_rejected_total``). Returns swap
        stats (``seconds``, ``in_flight`` at the boundary, ...). A
        timeout clears the request if the loop has not yet claimed
        it, so a later swap can be submitted.

        ``prepared=`` bypasses the prep: a device tree already in the
        engine's layout (``prepare_swap``'s output — or a RETAINED
        pre-swap ``engine.params``, which is how the canary rollout
        rolls a bad checkpoint back without re-reading disk)."""
        if prepared is not None:
            prepped = prepared
        else:
            sd = self._swap_state(checkpoint_or_state)
            try:
                prepped = self.engine.prepare_swap(sd)
            except Exception:
                _M_swap_rejected.inc()
                _flight.record("serving", "swap_end", ok=False,
                               error="prepare")
                raise
        done = threading.Event()
        slot: dict = {}
        with self._submit_lock:
            if self._stopping.is_set():
                raise RuntimeError(
                    "GenerationServer is shutting down; weights cannot "
                    "be swapped into a draining loop")
            if self._swap_req is not None:
                raise RuntimeError(
                    "a weight swap is already pending; wait for it "
                    "before submitting another")
            self._swap_req = (prepped, done, slot)
        self._q.put(self._STOP)  # wake an idle loop (sentinel no-op)
        if not done.wait(timeout):
            with self._submit_lock:
                cancelled = (self._swap_req is not None
                             and self._swap_req[1] is done)
                if cancelled:
                    self._swap_req = None
            raise TimeoutError(
                f"weight swap not applied within {timeout}s — "
                + ("cancelled before the loop claimed it"
                   if cancelled else
                   "the loop claimed it mid-apply; it may still land"))
        if "error" in slot:
            raise slot["error"]
        return slot["result"]

    def _shed(self) -> bool:
        """The STATIC load-shedding rule (ROADMAP 1c) — now the
        fallback policy behind ``serving_supervisor.StaticShedPolicy``
        (the default) and the adaptive policy's floor: shed
        when admission is block-starved (``serving.blocks_free`` at
        zero AND a request is already deferred on blocks — the
        signal that queue_seconds is about to climb) and the waiting
        backlog (deferred + queued, the ``queue_depth`` gauge's own
        sum — hold-the-line fairness keeps the deferred list itself
        at one) exceeds ``FLAGS_serving_shed_queue``. 0 (default)
        disables the policy — exhaustion defers unboundedly as
        before."""
        from .core.flags import flag_value
        bound = int(flag_value("serving_shed_queue"))
        if bound <= 0:
            return False
        return (self._waiting != []
                and self._q.qsize() + len(self._waiting) > bound
                and self.engine._kv.available_blocks() <= 0)

    def _expired(self, req) -> bool:
        return (req["expires"] is not None
                and time.monotonic() > req["expires"])

    def _drop_ahead(self, req) -> None:
        """A request ends here: whatever is still enqueued for it (a
        decode launch it was part of, its prompt's first token) will
        reach nobody."""
        ahead = req["launched"] - len(req["out"])
        if ahead > 0:
            _M_overrun.inc(ahead)
            req["launched"] = len(req["out"])

    def _fail(self, req, error) -> None:
        self._drop_ahead(req)
        req["error"] = error
        req["done"].set()
        _M_failed.inc()
        _flight.record(
            "serving",
            "expired" if isinstance(error, TimeoutError) else "failed",
            trace_id=req.get("trace_id"), error=type(error).__name__,
            tokens=len(req["out"]))
        self._observe_done(req)

    @staticmethod
    def _observe_done(req) -> None:
        """Request-completion telemetry: tokens delivered (partial counts
        too — a deadline-failed request keeps its tokens) + wall time +
        per-token latency, plus the queue/decode latency split."""
        tokens = len(req["out"])
        if tokens:
            _M_tokens.inc(tokens)
        now = time.monotonic()
        dt = now - req["t0"]
        _M_req_s.observe(dt)
        _M_token_s.observe(dt / max(tokens, 1))
        t_admit = req.get("t_admit")
        if t_admit is not None:
            _M_decode_s.observe(now - t_admit)
        else:
            # never admitted (deadline expired / cancelled while
            # queued): its whole life WAS queue time. Without this the
            # histogram only sees survivors — under the very overload
            # the metric exists to expose, the starved majority would
            # be censored and queue_seconds would stay low
            _M_queue_s.observe(dt)

    def _free_slots(self):
        eng = self.engine
        # a slot that has launched its last token has left the batch
        # and holds its request until that token is fetched
        return [s for s in range(eng.max_slots)
                if not eng.active[s] and s not in self._prefilling
                and s not in self._slots]

    def _admit_paged(self, req, slot) -> str:
        """Paged admission: allocate + reserve blocks and start the
        chunked prefill. Returns 'admitted', 'defer' (pool cannot
        cover the reservation yet — exhaustion queues, never
        crashes) or 'dropped' (sentinel/expired/failed)."""
        eng = self.engine
        if req is self._STOP or req["done"].is_set() \
                or self._fenced():
            return "dropped"
        if self._expired(req):
            self.deadline_expired += 1
            _M_expired.inc()
            self._fail(req, TimeoutError(
                "request deadline expired while queued"))
            return "dropped"
        try:
            # budget = REMAINING tokens: a crash-recovered request
            # re-admits with prompt + committed tokens as its prompt,
            # so reserving the full max_new again would over-draw the
            # pool for work already delivered (fresh requests have
            # empty out — identical behavior)
            ok = eng.begin_request(
                slot, req["prompt"],
                max(req["max_new"] - len(req["out"]), 1))
        except Exception as e:  # noqa: BLE001 — surfaced per request
            self._fail(req, e)
            return "dropped"
        if not ok:
            return "defer"
        req["t_admit"] = time.monotonic()
        req["launched"] = len(req["out"])
        # stamp admission BEFORE prefill: queue_seconds is the pure
        # submit->admission wait and decode_seconds covers prefill +
        # decode (slow prefill must not masquerade as queueing — the
        # load-shedding signal would point at admission when the real
        # cost is the model). t_queue0 rebases the origin for
        # crash-recovered requests: their pre-crash DECODE time is
        # not admission starvation
        _M_queue_s.observe(req["t_admit"] - req.get("t_queue0",
                                                    req["t0"]))
        # per-request prefix accounting: tokens this admission served
        # from shared radix blocks (0 = cold prompt), readable off the
        # finished request next to its tokens/latency (getattr:
        # duck-typed fake engines keep the bare contract)
        req["prefix_hit_tokens"] = getattr(
            eng, "prefix_hit_tokens", {}).get(slot, 0)
        self._prefilling[slot] = req
        self.admitted += 1
        _M_admitted.inc()
        _flight.record("serving", "admitted",
                       trace_id=req.get("trace_id"), slot=slot,
                       prefix_hit=req["prefix_hit_tokens"])
        return "admitted"

    def _admit(self):
        if self._cancel_waiting:
            # shutdown(drain=False) signalled: cancel block-deferred
            # requests HERE, on the loop thread — failing them from
            # the shutdown thread would race this function's
            # done-check-then-admit sequence (a request could be
            # cancelled and admitted simultaneously)
            for req in self._waiting:
                if not req["done"].is_set():
                    self._fail(req, RuntimeError(
                        "request cancelled: server shut down before "
                        "admission"))
            self._waiting = []
        free = self._free_slots()
        # block-deferred requests retry first, and HOLD THE LINE: while
        # any of them still cannot be covered, nothing newer is pulled
        # from the queue — otherwise a stream of small later requests
        # would keep re-consuming every freed block and starve a large
        # deferred request forever (fairness over utilization; the
        # backlog accrues queue_seconds and deadlines as usual)
        still: List[dict] = []
        for req in self._waiting:
            if req["done"].is_set():
                continue  # cancelled/expired while deferred
            if not free:
                still.append(req)
                continue
            verdict = self._admit_paged(req, free[0])
            if verdict == "admitted":
                free.pop(0)
            elif verdict == "defer":
                still.append(req)
        self._waiting = still
        while free and not self._waiting:
            try:
                req = self._q.get_nowait()
            except _queue.Empty:
                return
            verdict = self._admit_paged(req, free[0])
            if verdict == "admitted":
                free.pop(0)
            elif verdict == "defer":
                self._waiting.append(req)

    def _run_prefill(self) -> List[dict]:
        """Enqueue ONE prompt chunk of the OLDEST-admitted prefilling
        slot (dict insertion order — slot-index order would let a
        newer request admitted into a lower slot starve an older
        in-progress prefill) — the prefill/decode interleave: each
        loop iteration costs at most one chunk forward on top of the
        decode step, so already-admitted slots keep streaming. A
        prompt's last chunk is not waited for: its slot is in this
        pass's decode launch, and what comes back is the first token
        still to fetch ([] otherwise, and from an engine that fetched it
        itself)."""
        eng = self.engine
        halves = _steps_in_halves(eng)
        for slot in list(self._prefilling):
            req = self._prefilling[slot]
            tid = req.get("trace_id")
            with _span("serving.prefill", trace_id=tid,
                       slot=slot) as span:
                try:
                    first = (eng.prefill_enqueue if halves
                             else eng.prefill_chunk)(slot)
                except Exception as e:  # noqa: BLE001 — per-request
                    if self._fenced():
                        return []  # zombie: recovery owns the request now
                    del self._prefilling[slot]
                    eng.release(slot, evicted=True)
                    self._fail(req, e)
                    return []
                if self._fenced():
                    return []  # zombie woke from a wedged chunk: commit
                    # nothing — the new loop re-admitted this request
                # the turn this request got: which prompt tokens, in
                # which bucket (getattr: duck-typed fake engines keep
                # the bare contract)
                chunk = dict(getattr(eng, "last_chunk", {}))
                span.set(**chunk)
                _flight.record("serving", "prefill_chunk", trace_id=tid,
                               slot=slot, **chunk)
                if first is None:
                    return []
                del self._prefilling[slot]
                self._slots[slot] = req
                req["launched"] += 1
            unfetched = {"slot": slot, "req": req, "handle": first}
            if halves:
                return [unfetched]
            # prefill_chunk() fetched the token itself
            self._commit_first(unfetched, first)
            return []
        return []

    def _commit_first(self, first: dict, tok: int) -> None:
        """A prompt's first token has been fetched: it goes to the
        request its prompt was, if that still holds the slot."""
        slot, req = first["slot"], first["req"]
        if self._slots.get(slot) is not req or req["done"].is_set():
            return  # ended meanwhile: counted where it ended
        req["out"].append(int(tok))
        _flight.record("serving", "prefilled",
                       trace_id=req.get("trace_id"), slot=slot,
                       prompt_len=int(req["prompt"].shape[0]))
        self._finish_if_done(slot, req)

    def _finish_if_done(self, slot, req):
        eng = self.engine
        # capacity ends a request once its last launched token is here
        done = (len(req["out"]) >= req["max_new"]
                or (eng.eos_id is not None
                    and req["out"][-1] == eng.eos_id)
                or (req["launched"] <= len(req["out"])
                    and eng.pos[slot] >= eng.max_seq - 1))
        if done:
            self._drop_ahead(req)
            eng.release(slot)
            del self._slots[slot]
            req["done"].set()
            _flight.record("serving", "finished",
                           trace_id=req.get("trace_id"),
                           tokens=len(req["out"]))
            self._observe_done(req)
        return done

    def _expire_active(self):
        """Step-boundary deadline sweep over active, prefilling and
        block-waiting requests: an expired request is failed with
        TimeoutError and its slot/blocks freed (its blocks count as
        EVICTIONS — serving.block_evictions_total); tokens already
        produced stay in ``req['out']``."""
        for slot in list(self._slots):
            req = self._slots[slot]
            if self._expired(req):
                self.deadline_expired += 1
                _M_expired.inc()
                self.engine.release(slot, evicted=True)
                del self._slots[slot]
                self._fail(req, TimeoutError(
                    f"request deadline expired after "
                    f"{len(req['out'])} token(s)"))
        for slot in list(self._prefilling):
            req = self._prefilling[slot]
            if self._expired(req):
                self.deadline_expired += 1
                _M_expired.inc()
                self.engine.release(slot, evicted=True)
                del self._prefilling[slot]
                self._fail(req, TimeoutError(
                    "request deadline expired during prefill"))
        still = []
        for req in self._waiting:
            if not req["done"].is_set() and self._expired(req):
                self.deadline_expired += 1
                _M_expired.inc()
                self._fail(req, TimeoutError(
                    "request deadline expired waiting for KV blocks"))
            elif not req["done"].is_set():
                still.append(req)
        self._waiting = still

    def _expire_queued(self):
        """Fail expired requests still WAITING in the queue — even when
        every slot is busy, a starved request's caller is unblocked at
        the next step boundary, not when a slot eventually frees. The
        failed entry stays enqueued; _admit() discards it on dequeue."""
        with self._q.mutex:
            waiting = list(self._q.queue)
        for req in waiting:
            if req is not self._STOP and not req["done"].is_set() \
                    and self._expired(req):
                self.deadline_expired += 1
                _M_expired.inc()
                self._fail(req, TimeoutError(
                    "request deadline expired while queued"))

    def _apply_pending_swap(self) -> None:
        """Apply a pending weight hot-swap HERE, on the loop thread,
        at a step boundary: the previous decode step has fully
        committed its tokens and no new step has dispatched (the loop
        lands the launch it keeps in flight before it calls this), so no
        in-flight request drops or corrupts a token — its KV blocks
        and slot state are untouched and the next step simply runs on
        the new weights. A rejected swap (engine validation) leaves
        the old weights installed and the loop running."""
        if self._swap_req is None:
            return
        with self._submit_lock:  # claim races a caller-side timeout
            req = self._swap_req
            self._swap_req = None
        if req is None:
            return
        prepped, done, slot = req
        t0 = time.perf_counter()
        _flight.record("serving", "swap_begin",
                       in_flight=len(self._slots),
                       prefilling=len(self._prefilling))
        try:
            self.engine.swap_weights(prepared=prepped)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            _M_swap_rejected.inc()
            _flight.record("serving", "swap_end", ok=False,
                           error=type(e).__name__)
            slot["error"] = e
            done.set()
            return
        dt = time.perf_counter() - t0
        self.weight_swaps += 1
        _M_swaps.inc()
        _M_swap_s.observe(dt)
        _flight.record("serving", "swap_end", ok=True,
                       seconds=round(dt, 4))
        slot["result"] = {"seconds": dt,
                          "in_flight": len(self._slots),
                          "prefilling": len(self._prefilling),
                          "steps_run": self.steps_run}
        done.set()

    def _admit_spanned(self, admit, *args) -> None:
        """Run an admission pass (`_admit`, or `_admit_parked` from the
        idle loop) as a `serving.admit` span that says how many requests
        it admitted."""
        with _span("serving.admit") as span:
            before = self.admitted
            admit(*args)
            span.set(admitted=self.admitted - before)

    def _admit_parked(self, req) -> None:
        """Admit the request the idle loop was parked for, directly."""
        if self._admit_paged(req, self._free_slots()[0]) == "defer":
            self._waiting.append(req)

    def _launch_counts(self) -> Dict[str, int]:
        """What the next decode launch reads, counted before it runs:
        `rows` active slots, `live_tokens` = sum over them of `pos + 1`
        (each row's history and the token it writes), `max_ctx` the
        longest of them, and `walk_tokens`, what the paged kernel
        walks for them: each slot's `pos + 1` rounded up to the
        kernel's group of blocks (left out where the model's cache has
        no pool that kernel walks). `live_tokens / walk_tokens` is the
        live share of the walk; `rows * max_ctx` is what walking every
        slot to the longest context cost."""
        eng = self.engine
        ctx = np.asarray(eng.pos)[np.asarray(eng.active, bool)] + 1
        out = {"rows": int(ctx.size), "live_tokens": int(ctx.sum()),
               "max_ctx": int(ctx.max()) if ctx.size else 0}
        # an engine that is not this module's has no kernel to ask
        group = getattr(eng, "walk_group_tokens", lambda: 1)()
        if group is not None:
            out["walk_tokens"] = int((-(-ctx // group) * group).sum())
        window = getattr(eng, "window", None)
        if window is not None:
            # what a window layer reads of them: the last `window` each
            out["window_tokens"] = int(np.minimum(ctx, window).sum())
        select_k = getattr(eng, "select_k", None)
        if select_k is not None:
            # what a layer that attends selected positions reads of them
            out["selected_tokens"] = int(np.minimum(ctx, select_k).sum())
        index_layers = getattr(eng, "index_layers", 0)
        if index_layers:
            # the key blocks the index kernel copies for them: each slot's
            # blocks up to its row, in every indexer layer
            out["index_blocks"] = int(
                (-(-ctx // eng.block_size)).sum()) * index_layers
        if getattr(eng, "_stateful", False):
            # slots whose state the launch reads and writes, a state layer
            out["state_slots"] = int(ctx.size)
        return out

    def _sweep(self) -> None:
        """The step boundary's housekeeping, as one span: deadlines,
        then gauges AFTER the completion/expiry sweep (a scrape between
        steps must not report finished requests as in-flight), then the
        admission policy's evidence (EWMAs of blocks/backlog/throughput)
        and its brownout/shed levels."""
        with _span("serving.sweep"):
            self._expire_active()
            self._expire_queued()
            self._set_gauges()
            self.policy.on_step(self)

    def _leave_by_count(self) -> None:
        """Before a launch is enqueued: a slot whose request has its
        last token launched (``max_new`` of them, or the cache's last
        position) leaves the batch by counting, without waiting to see
        that token; it holds its request until the token is fetched.
        Only an EOS needs the token: it is seen one launch late, and the
        slot's one launch too many is an overrun."""
        eng = self.engine
        for slot, req in self._slots.items():
            if eng.active[slot] and (req["launched"] >= req["max_new"]
                                     or eng.pos[slot] >= eng.max_seq - 1):
                eng.leave(slot)

    def _enqueue_decode(self, ahead: bool) -> dict:
        """Enqueue the next decode launch for every active slot. The
        launch carries what it was launched for: which request each
        slot stepped. An engine without the two halves steps here,
        enqueue and collect as one call, and its tokens are in the
        launch already."""
        eng = self.engine
        launch = {"toks": None,
                  "reqs": {slot: req for slot, req in self._slots.items()
                           if eng.active[slot]}}
        if _steps_in_halves(eng):
            launch["launch"] = eng.step_enqueue()
            self.launched_ahead += ahead
        else:
            launch["toks"] = eng.step()
        for req in launch["reqs"].values():
            req["launched"] += 1
        return launch

    def _fetch(self, firsts, launch, span=None):
        """Wait for what is in flight, in the order it was enqueued:
        the first tokens of the prompts whose last chunk went out before
        ``launch``, each committed as soon as it is here, then the
        decode ``launch``, whose tokens come back for ``_commit``. A
        model's counts, which the collects return, ride on ``span``,
        each launch's once: what is fetched under no launch's span
        waits for the next."""
        eng = self.engine
        toks = None
        for first in firsts:
            tok, counts = eng.prefill_collect(first["handle"])
            self._carry(counts)
            if self._fenced():
                return None
            self._commit_first(first, tok)
        if launch is not None:
            toks, counts = eng.step_collect(launch["launch"])
            self._carry(counts)
        if span is not None and self._aux_carry:
            span.set(**self._aux_carry)
            self._aux_carry = {}
        return toks

    def _carry(self, counts: Dict[str, int]) -> None:
        for key, n in counts.items():
            self._aux_carry[key] = self._aux_carry.get(key, 0) + n

    def _commit(self, launch, toks, counts=None) -> None:
        """Deliver a fetched launch's tokens (``counts`` a slot: more
        than one after a speculative step), each to the request it was
        launched for, if that still holds the slot and is alive: what
        was launched for a request that has ended since was counted as
        overrun there, and reaches nobody."""
        eng = self.engine
        self.steps_run += 1
        _M_steps.inc()
        with _span("serving.commit") as span:
            delivered = self.tokens_delivered
            for slot, req in launch["reqs"].items():
                if self._slots.get(slot) is not req \
                        or req["done"].is_set():
                    continue
                before = len(req["out"])
                for tok in (toks[slot:slot + 1] if counts is None
                            else toks[slot, :int(counts[slot])]):
                    req["out"].append(int(tok))
                    if len(req["out"]) >= req["max_new"]:
                        break
                    if eng.eos_id is not None and tok == eng.eos_id:
                        break
                if counts is not None:      # fetched as it was launched
                    req["launched"] = len(req["out"])
                self.tokens_delivered += len(req["out"]) - before
                _flight.record("serving", "decode",
                               trace_id=req.get("trace_id"),
                               step=self.steps_run,
                               tokens=len(req["out"]))
                self._finish_if_done(slot, req)
            span.set(tokens=self.tokens_delivered - delivered)

    def _step_spec(self) -> None:
        """A speculative iteration, fetched where it is launched: up to
        spec_k committed tokens per slot for one step's host fetch; the
        greedy stream is bit-equal to plain stepping, so requests cut
        off mid-window (eos / budget) see exactly the tokens they would
        have anyway."""
        with _span("serving.decode", step=self.steps_run + 1, spec=1,
                   **self._launch_counts()):
            toks, counts = self.engine.spec_step()
        if not self._fenced():
            self._commit({"reqs": dict(self._slots)}, toks, counts)

    def _step_ahead(self, firsts, ahead, new_firsts):
        """A pass's decode step: launch n+1 goes out, then launch n
        (``ahead``) and the ``firsts`` of the pass before come in and
        are committed, so the device always holds its next program when
        one ends. Returns what the pass leaves in flight: its launch
        and the first tokens of its prompt chunk (``new_firsts``)."""
        eng = self.engine
        self._leave_by_count()
        launch = None
        if np.any(eng.active):
            with _span("serving.decode",
                       step=self.steps_run + 1 + (ahead is not None),
                       spec=0, **self._launch_counts()) as span:
                launch = self._enqueue_decode(ahead is not None)
                if ahead is not None:
                    span.set(ahead=1)
                toks = self._fetch(firsts, ahead, span)
        else:   # every slot has launched its last token
            toks = self._fetch(firsts, ahead)
        if self._fenced():
            return None, []
        if ahead is not None:
            self._commit(ahead, toks)
        if launch is not None and launch["toks"] is not None:
            # the engine stepped in one call: nothing stays in flight
            # (and _run_prefill has committed its prompt's first token)
            self._commit(launch, launch["toks"])
            return None, []
        return launch, new_firsts

    def _land(self, firsts, launch, new_firsts=()):
        """Fetch and commit what is in flight, outside a launch and in
        the order it was enqueued (``firsts``, ``launch``, then this
        pass's ``new_firsts``): where the loop must not run ahead (a
        weight swap, a speculative step, no batch left) it lands
        everything first. Returns what is in flight then: no launch, no
        first token."""
        toks = self._fetch(firsts, launch)
        if launch is not None and not self._fenced():
            self._commit(launch, toks)
        if new_firsts and not self._fenced():
            self._fetch(new_firsts, None)
        return None, []

    def _loop(self):
        # the epoch captured here fences THIS incarnation: after a
        # supervisor restart (crash or stall), a zombie of the old
        # loop that wakes up sees a newer epoch and exits without
        # touching slots, engine state, or the queue (the thread
        # stamp lets the admit/prefill helpers check the same fence
        # from inside a call the zombie was wedged in)
        my_epoch = self._epoch
        threading.current_thread()._serving_loop_epoch = my_epoch
        # what this incarnation has enqueued and not fetched: the decode
        # launch a pass leaves in flight, and the first tokens of the
        # prompts whose last chunk it enqueued. A fenced loop takes them
        # with it: the loop that replaces it starts with neither
        ahead: Optional[dict] = None
        firsts: List[dict] = []
        while True:
            if self._epoch != my_epoch:
                return  # fenced: a supervisor replaced this loop
            self._beat = time.monotonic()  # stall-watchdog heartbeat
            try:
                # one root span per pass; every child is on this thread,
                # so a trace's idle gaps read admit / prefill / decode /
                # commit / sweep instead of "host, unattributed"
                with _span("serving.iter", step=self.steps_run,
                           active=len(self._slots),
                           prefilling=len(self._prefilling),
                           waiting=self._q.qsize() + len(self._waiting)):
                    if self._swap_req is not None:
                        # "no new step has dispatched": the swap sees
                        # every launched token committed
                        ahead, firsts = self._land(firsts, ahead)
                        if self._epoch != my_epoch:
                            return
                        with _span("serving.swap"):
                            self._apply_pending_swap()
                    self._admit_spanned(self._admit)
                    new_firsts = self._run_prefill() \
                        if self._prefilling else []
                    if not self._slots:
                        # whatever is in flight belongs to nobody any
                        # more (its requests ended on an EOS or the
                        # clock): land it before the loop cycles, parks
                        # or leaves
                        ahead, firsts = self._land(firsts, ahead)
                        if self._epoch != my_epoch:
                            return
                        if self._prefilling or self._waiting:
                            # prompts still chunking / requests waiting
                            # on blocks: keep cycling (no decode batch
                            # yet)
                            self._sweep()
                            continue
                        if self._stopping.is_set() and self._q.empty():
                            break  # drained: nothing active or queued
                        # idle: block for the next request and admit it
                        # DIRECTLY — a get-then-requeue would let
                        # requests submitted in the window jump ahead
                        # of it (FIFO)
                        self._set_gauges()  # idle: a scrape must read 0
                        self._idle = True   # parked, not stalled
                        try:
                            with _span("serving.idle"):
                                req = self._q.get()
                        finally:
                            self._idle = False
                        if self._epoch != my_epoch:
                            # fenced while parked: the request belongs
                            # to the NEW loop — hand it back and exit
                            if req is not self._STOP:
                                self._q.put(req)
                            return
                        if req is self._STOP:
                            continue
                        self._admit_spanned(self._admit_parked, req)
                        continue
                    # fault-injection site: a kill-point armed here
                    # simulates a crash mid-decode — the loop thread
                    # dies (KillPoint is a BaseException) and the
                    # flight recorder's threading.excepthook dump
                    # carries every in-flight request's lifecycle trail
                    _fi.fire("serving.decode")
                    eng = self.engine
                    if eng.spec_ready():
                        # the accepted count decides pos, and the draft
                        # reads the host's last_ids: a speculative step
                        # starts from everything landed
                        ahead, firsts = self._land(
                            firsts, ahead, new_firsts)
                        new_firsts = []
                        if self._epoch != my_epoch:
                            return
                    if self._slots and eng.spec_ready():
                        self._step_spec()
                    else:
                        ahead, firsts = self._step_ahead(
                            firsts, ahead, new_firsts)
                    if self._epoch != my_epoch:
                        return  # fenced mid-step (stall restart): the
                        # new loop owns the slots — nothing was committed
                    self._sweep()
            except Exception as e:  # noqa: BLE001 — fail loudly, stay up
                if self._epoch != my_epoch:
                    return  # fenced: the slots hold RE-ADMITTED
                    # requests now — failing them here would double
                    # their terminal events
                _flight.record("serving", "loop_error",
                               error=type(e).__name__)
                for slot, req in list(self._slots.items()):
                    self._fail(req, e)
                    self.engine.release(slot, evicted=True)
                self._slots.clear()
                for slot, req in list(self._prefilling.items()):
                    self._fail(req, e)
                    self.engine.release(slot, evicted=True)
                self._prefilling.clear()
                ahead, firsts = None, []
                self._set_gauges()
        self._set_gauges()
        # a swap still pending at loop exit can never apply: unblock
        # its caller with the reason instead of letting it time out
        req = self._swap_req
        if req is not None:
            self._swap_req = None
            req[2]["error"] = RuntimeError(
                "server shut down before the weight swap applied")
            req[1].set()
        self._drained.set()

    def _set_gauges(self) -> None:
        # block-deferred requests are still queued work: a scrape must
        # see them (queue_seconds keeps accruing for them too)
        _G_queue.set(self._q.qsize() + len(self._waiting))
        _G_inflight.set(len(self._slots) + len(self._prefilling))
        pools = getattr(self.engine, "pool_blocks_in_use", None)
        if pools is not None:           # an engine of this module
            blocks = pools()
            if blocks != self._pool_gauged:     # only when a count moved
                self._pool_gauged = blocks
                self.engine._sc.set_pool_gauges(blocks)
            state = self.engine.state_stats()
            if state and state != self._state_gauged:
                self._state_gauged = state
                _G_state_slots.set(state["state_slots_in_use"])
                _G_state_bytes.set(state["state_bytes"])

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = 300.0) -> bool:
        """Stop the server. ``drain=True`` (default) lets in-flight and
        already-queued requests finish while new submissions are
        rejected; ``drain=False`` additionally cancels everything still
        waiting in the queue (active requests still finish — a decode
        step cannot be abandoned mid-flight without corrupting slots).
        Returns True once the loop has fully drained."""
        with self._submit_lock:
            self._stopping.set()
        if not drain:
            # cancel queued work; requests already in slots complete.
            # Queue pops are atomic (whoever pops a request owns
            # failing it), but the _waiting list belongs to the loop
            # thread — signal it to cancel those at its next admission
            # pass instead of racing its done-check-then-admit sequence
            self._cancel_waiting = True
            while True:
                try:
                    req = self._q.get_nowait()
                except _queue.Empty:
                    break
                if req is not self._STOP:
                    self._fail(req, RuntimeError(
                        "request cancelled: server shut down before "
                        "admission"))
        self._q.put(self._STOP)  # wake an idle loop
        # Event.wait(None) blocks until drained — timeout=None means
        # "wait as long as it takes", never "skip the wait"
        drained = self._drained.wait(timeout)
        if self._metrics_server is not None:
            try:
                self._metrics_server.close()
            finally:
                self._metrics_server = None
        return drained

    @staticmethod
    def trace(request_id) -> List[dict]:
        """The flight-recorder lifecycle trail of ONE request — submit,
        queued, admitted, per-step decode, finished/expired/failed —
        live from the in-process ring (a crash dump carries the same
        events). ``request_id`` is the ``trace_id`` string or the req
        dict :meth:`submit` returned."""
        tid = (request_id.get("trace_id")
               if isinstance(request_id, dict) else request_id)
        return _flight.events(trace_id=tid)

    def stats(self) -> Dict[str, int]:
        with self._q.mutex:  # don't count _STOP sentinels as work
            queued = sum(1 for r in self._q.queue
                         if r is not self._STOP
                         and not r["done"].is_set())
        out = {"steps_run": self.steps_run,
               "launched_ahead": self.launched_ahead,
               "admitted": self.admitted,
               "rejected": self.rejected, "shed": self.shed,
               "deadline_rejected": self.deadline_rejected,
               "deadline_expired": self.deadline_expired,
               "weight_swaps": self.weight_swaps,
               "tokens_delivered": self.tokens_delivered,
               "loop_restarts": self.loop_restarts,
               "recovered": self.recovered,
               "quarantined": self.quarantined,
               "crashed": int(self._crashed),
               "in_flight": len(self._slots), "queued": queued,
               "prefilling": len(self._prefilling),
               "waiting_for_blocks": len(self._waiting),
               "draining": int(self._stopping.is_set()),
               "drained": int(self._drained.is_set()),
               "kv_pool": self.engine._kv.stats()}
        state = getattr(self.engine, "state_stats", dict)()
        if state:       # beside the blocks: the state's slots and bytes
            out["kv_pool"] = dict(out["kv_pool"], **state)
        # where the host paused: the process's collections by generation
        # ([collections, seconds, longest ms]), the engine's fetch stalls
        out["host"] = {"gc": _host.gc_stats(),
                       **getattr(self.engine, "host_stats", dict)()}
        return out
