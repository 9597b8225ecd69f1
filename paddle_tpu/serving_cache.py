"""Paged KV cache for generation serving: block pool, block tables,
and the tiled block-table-gathered streaming attention step.

A cache of `max_seq` K/V rows a slot a layer burns HBM proportional to
*capacity*, whether a slot holds a 4-token prompt or a full context.
This module keeps a **shared per-layer block pool**
``[num_blocks, block_size, KVH*D]`` (the KV heads side by side in the
minor dimension: the layout the Pallas kernel's block copies read, so
no launch relayouts a pool) plus per-slot **block tables**
mapping logical block index -> physical block, so HBM scales with
*active tokens* and a pool sized for N full-length slots admits far
more short requests (the vLLM design; here grounded in the
FlashAttention-2/CUTLASS memory-streaming tiling of PAPERS.md).

Three pieces live here, deliberately factored apart:

- :class:`PagedKVCache` — the HOST side: a free-list block allocator
  with admission-time budget *reservations* (a request is admitted
  only if its worst-case block count fits, so extension at step
  boundaries can never fail mid-decode), per-slot block tables, and
  the block-pool telemetry (``serving.blocks_free`` /
  ``blocks_used`` gauges, ``block_evictions_total`` counter, flight
  events for alloc/free/exhaustion). With
  ``FLAGS_serving_prefix_cache`` (default on) it additionally keeps a
  **content-addressed radix tree** over committed prompt blocks:
  nodes are keyed by ``block_size``-token id chunks and own
  refcounted physical blocks, so admission can alias a hot prefix
  into a new slot's table instead of re-prefilling it (see
  :class:`_PrefixNode` and ``PagedKVCache.admit``'s ``token_ids``).
  Released prefixes stay cached at refcount 0 and are LRU-evicted
  when the free list runs dry (``block_evictions_total``, flight
  ``prefix_evict``).
- :func:`paged_attention` — the DEVICE side: a tiled, online-softmax
  streaming attention step that walks a slot's block list one
  ``block_size`` tile at a time, never materializing a dense
  ``[S, max_seq]`` score or cache view. Pure jnp on the tier-1/CPU
  path; the tiling is factored as one function with a flat
  (q, pools, tables, positions) signature precisely so a Pallas TPU
  kernel can drop in behind the same seam (ROADMAP item 3's
  block-table-aware variant).
- :func:`write_kv_tokens` / :func:`absmax_quantize` — the scatter of
  freshly computed K/V rows into (physical block, offset) cells, with
  optional int8 block storage using the same symmetric absmax math as
  ``quantization/quantize.py``'s ``quant_absmax`` (dynamic per-token
  per-head scales, calibration-free because decode K/V are visible).
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .observability import flight as _flight
from .observability import metrics as _om

__all__ = ["PagedKVCache", "KindedKVCache", "SlotStates", "paged_attention",
           "write_kv_tokens", "absmax_quantize", "use_kernel_default",
           "copy_block"]

_M = _om.scope("serving")
_G_blocks_free = _M.gauge(
    "blocks_free",
    "Paged KV pool blocks available for admission (free minus "
    "outstanding budget reservations)")
_G_blocks_used = _M.gauge(
    "blocks_used", "Paged KV pool blocks physically mapped to slots")
_M_evictions = _M.counter(
    "block_evictions_total",
    "Paged KV blocks reclaimed from expired/failed/cancelled requests "
    "(normal completion frees blocks without counting here)")
_G_kind_blocks = _M.gauge(
    "kv_blocks_in_use",
    "Paged KV blocks mapped to slots, by the kind of layer whose table "
    "holds them (`kind`: full / window; a block of a kind is one block "
    "in each of that kind's layers) and by the pool they lie in (`pool`: "
    "k, v, latent, index ...; summed over the layers that own one)")
_M_window_freed = _M.counter(
    "kv_window_blocks_freed_total",
    "Blocks a window layer's table gave back because every position in "
    "them had fallen behind the attention window of a live request")


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


class _PrefixNode:
    """One radix-tree node: the edge from ``parent`` is labeled by a
    full ``block_size``-token id chunk (``key``) and owns exactly one
    physical block holding that chunk's K/V rows. ``ref`` counts the
    slot tables currently aliasing the block (NOT including the cache
    itself): ref 0 means *cached* — still matchable, reclaimable by
    the LRU eviction pass when the free list runs dry. ``stamp`` is a
    monotonic last-release tick, so eviction is leaf-first
    least-recently-released.

    Invariant (every match/release refs the WHOLE path root->node):
    ``parent.ref >= child.ref`` — a ref-0 node's entire subtree is
    ref 0, so counting ref-0 nodes counts exactly the reclaimable
    supply."""

    __slots__ = ("key", "parent", "children", "block", "ref", "stamp")

    def __init__(self, key: Optional[tuple], parent: "_PrefixNode",
                 block: int = -1):
        self.key = key
        self.parent = parent
        self.children: Dict[tuple, "_PrefixNode"] = {}
        self.block = block
        self.ref = 0
        self.stamp = 0


class PagedKVCache:
    """Host-side paged-KV bookkeeping: free-list allocator + block
    tables + budget reservations.

    The invariant that makes mid-decode exhaustion impossible:
    ``len(free) >= reserved_total`` at all times. ``admit`` only
    succeeds when the request's WORST-CASE block count (prompt +
    generation budget) fits into ``free - reserved_total``; blocks
    for the prompt are mapped immediately, the rest stay *reserved*
    and are materialized one at a time by ``ensure_token`` as decode
    crosses block boundaries. ``release`` returns both.

    A physical block id indexes the engine's device pools, one K and
    one V a layer, each ``[num_blocks, block_size, KVH*D]``: a block is
    one contiguous ``[block_size, KVH*D]`` slab, which is what the
    kernel copies (``T(8,128)(2,1)`` on the v5e, where a ``[..., KVH,
    128]`` bf16 array would be tiled ``T(4,128)(2,1)`` and need a copy).

    Thread safety: mutations are guarded by an instrumented lock
    (``analysis.locks.make_lock``) — the server loop is the only
    writer in production, but tests and direct engine use may churn
    from other threads.
    """

    def __init__(self, max_slots: int, max_seq: int, block_size: int,
                 num_blocks: int,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 window: Optional[int] = None, window_slack: int = 0,
                 kind: str = "full"):
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.max_blocks_per_slot = _ceil_div(max_seq, self.block_size)
        # a WINDOW table (layers that attend the last `window` positions
        # only): a slot holds the blocks its live rows can still see and
        # no more — `advance` frees whole blocks behind the window and
        # maps the ones ahead, so what a slot may hold at once is capped:
        # the window, the rows one launch writes (`window_slack`: the
        # prefill chunk) and one block of ragged ends
        self.kind = str(kind)
        self.window = None if window is None else int(window)
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.hold_blocks = self.max_blocks_per_slot \
            if self.window is None else min(
                self.max_blocks_per_slot,
                _ceil_div(self.window + max(int(window_slack), 1),
                          self.block_size) + 1)
        self._need: Dict[int, int] = {}   # slot -> logical blocks in all
        self._lo: Dict[int, int] = {}     # slot -> first live logical block
        # None: what every slot may hold at once (dense capacity parity)
        self.num_blocks = int(max_slots) * self.hold_blocks \
            if num_blocks is None else int(num_blocks)
        if self.num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        # logical block index -> physical block id; -1 = unmapped. The
        # decode step receives this (as a device array) every step and
        # drops writes/reads through unmapped entries.
        self.block_tables = np.full(
            (int(max_slots), self.max_blocks_per_slot), -1, np.int32)
        # LIFO free list popping block 0 first (stable tests/debug)
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._owned: Dict[int, List[int]] = {}
        self._reserved: Dict[int, int] = {}
        self._reserved_total = 0
        self.evictions = 0
        # -- prefix radix cache (FLAGS_serving_prefix_cache) ----------
        from .core.flags import flag_value
        if self.window is not None:
            # a shared prefix block would have to outlive the window of
            # its first writer and be re-admitted behind another
            # request's window: not built, so never silently wrong
            if prefix_cache:
                raise ValueError(
                    "prefix sharing is not supported on a window "
                    "layer's block table: its blocks are freed behind "
                    "the window, a shared prefix must not be")
            prefix_cache = False
        self.prefix_enabled = bool(
            flag_value("serving_prefix_cache") if prefix_cache is None
            else prefix_cache)
        self.prefix_cap = int(
            flag_value("serving_prefix_cache_blocks")
            if prefix_cache_blocks is None else prefix_cache_blocks)
        self._root = _PrefixNode(None, None)  # type: ignore[arg-type]
        self._by_block: Dict[int, _PrefixNode] = {}
        self._evictable = 0                # tree nodes at ref 0
        self._stamp = itertools.count(1)   # LRU release ticks
        self._shared: Dict[int, List[int]] = {}   # slot -> aliased blocks
        self._tail: Dict[int, _PrefixNode] = {}   # slot -> deepest node
        self._matched: Dict[int, int] = {}        # slot -> skip tokens
        self._cow_pending: Dict[int, Tuple[int, int]] = {}
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        from .analysis.locks import make_lock
        self._lock = make_lock("serving.kv_pool")
        self._sync_gauges()

    # -- accounting ---------------------------------------------------------
    def available_blocks(self) -> int:
        """Blocks an admission may still claim: free plus the ref-0
        cached prefix blocks the LRU pass can reclaim, minus
        outstanding reservations. Shared (aliased) blocks count
        exactly once — aliasing a cached prefix consumes no supply."""
        return len(self._free) + self._evictable - self._reserved_total

    def used_blocks(self) -> int:
        """Blocks doing LIVE work — held privately by a slot or
        aliased by at least one (ref > 0). Ref-0 cached prefix blocks
        are NOT used: they are reclaimable supply the LRU pass hands
        back under pressure (``blocks_cached`` counts them)."""
        return self.num_blocks - len(self._free) - self._evictable

    def cached_blocks(self) -> int:
        """Blocks held by the prefix radix tree (shared + ref-0)."""
        return len(self._by_block)

    def occupied_slots(self) -> int:
        """Slots currently holding blocks (private or aliased)."""
        return len(set(self._owned) | set(self._shared))

    def stats(self) -> Dict[str, int]:
        return {"num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "blocks_free": len(self._free),
                "blocks_available": self.available_blocks(),
                "blocks_used": self.used_blocks(),
                "blocks_reserved": self._reserved_total,
                "blocks_cached": len(self._by_block),
                "blocks_evictable": self._evictable,
                "prefix_hits": self.prefix_hits,
                "prefix_tokens_reused": self.prefix_tokens_reused,
                "evictions": self.evictions}

    def _sync_gauges(self) -> None:
        _G_blocks_free.set(self.available_blocks())
        _G_blocks_used.set(self.used_blocks())
        _G_kind_blocks.set(self.used_blocks(), kind=self.kind)

    # -- prefix radix tree (lock held for every _-helper) -------------------
    def _incref(self, node: _PrefixNode) -> None:
        if node.ref == 0:
            self._evictable -= 1
        node.ref += 1

    def _decref(self, node: _PrefixNode) -> None:
        node.ref -= 1
        assert node.ref >= 0, "prefix refcount underflow"
        if node.ref == 0:
            node.stamp = next(self._stamp)
            self._evictable += 1

    def _match_path(self, token_ids) -> List[_PrefixNode]:
        """Walk the tree with consecutive full-block token chunks;
        returns the matched node path (possibly empty)."""
        ids = [int(t) for t in token_ids]
        node, path = self._root, []
        for i in range(len(ids) // self.block_size):
            child = node.children.get(
                tuple(ids[i * self.block_size:(i + 1) * self.block_size]))
            if child is None:
                break
            path.append(child)
            node = child
        return path

    def _evict_one(self) -> Optional[int]:
        """Reclaim the least-recently-released ref-0 LEAF (children
        keep their parent's block reachable; the parent becomes a leaf
        once they go). Returns the freed physical block, or None when
        nothing is evictable."""
        best = None
        for node in self._by_block.values():
            if node.ref == 0 and not node.children and \
                    (best is None or node.stamp < best.stamp):
                best = node
        if best is None:
            return None
        del best.parent.children[best.key]
        del self._by_block[best.block]
        self._evictable -= 1
        self.evictions += 1
        _M_evictions.inc()
        _flight.record("serving", "prefix_evict", block=best.block,
                       depth_key_tokens=len(best.key))
        return best.block

    def _pop_block(self) -> int:
        """One free block, evicting a cached prefix block if the free
        list is dry. Exhaustion here is a caller bug — every draw is
        covered by an admission-time reservation, and reservations are
        only granted against ``free + evictable``."""
        if self._free:
            return self._free.pop()
        b = self._evict_one()
        if b is None:
            raise RuntimeError(
                "KV block pool over-drawn: no free block and no "
                "evictable cached prefix — a reservation was granted "
                "against supply that no longer exists")
        return b

    # -- allocator ----------------------------------------------------------
    def admit(self, slot: int, prompt_tokens: int,
              total_tokens: int, token_ids=None) -> bool:
        """Admit a request into ``slot``: map blocks for its
        ``prompt_tokens`` now and reserve the rest of its
        ``total_tokens`` worst case. Returns False (request should
        wait) when the pool cannot cover the reservation; raises
        ValueError when it NEVER could (need exceeds the whole pool),
        so an impossible request fails loudly instead of queueing
        forever.

        With ``token_ids`` (the prompt) and the prefix cache on, the
        prompt is first matched against the radix tree: matched blocks
        are ALIASED into the slot's table with refcount bumps and the
        admission charges only the unshared remainder — the caller
        reads ``matched_tokens(slot)`` to skip their prefill. A match
        covering the whole (block-aligned) prompt keeps its last block
        only as a copy-on-write source: prefill must still produce the
        first generated token from position n-1, whose K/V write may
        not land in a shared block — the boundary block is copied at
        admission (one extra charged block; ``take_cow`` hands the
        (src, dst) pair to the engine's device-copy seam) and the
        match is credited as n-1 tokens."""
        slot = int(slot)
        prompt_tokens = int(prompt_tokens)
        now = _ceil_div(max(prompt_tokens, 1), self.block_size)
        total = min(max(_ceil_div(total_tokens, self.block_size), now),
                    self.max_blocks_per_slot)
        need = total
        # a window table holds at most `hold_blocks` of them at once;
        # the head of a long prompt is mapped now, `advance` moves on
        total = min(total, self.hold_blocks)
        now = min(now, total)
        with self._lock:
            if total > self.num_blocks:
                raise ValueError(
                    f"request needs {total} KV blocks "
                    f"({total_tokens} tokens at block_size "
                    f"{self.block_size}) but the pool holds only "
                    f"{self.num_blocks}; raise FLAGS_serving_num_blocks "
                    f"or shrink the request")
            if slot in self._owned or slot in self._shared:
                raise ValueError(f"slot {slot} already holds KV blocks")
            path: List[_PrefixNode] = []
            if self.prefix_enabled and token_ids is not None:
                path = self._match_path(token_ids)
            matched = len(path)
            # a full block-aligned match still re-runs the LAST prompt
            # token (its logits seed generation), so the boundary block
            # needs a private copy-on-write clone
            cow = matched > 0 and matched * self.block_size \
                >= prompt_tokens
            # incref BEFORE allocating: the allocation below may evict
            # ref-0 nodes, which must never include our matched path
            for node in path:
                self._incref(node)
            reserved = total - now
            need_now = now - matched + (1 if cow else 0)
            if need_now + reserved > len(self._free) + self._evictable \
                    - self._reserved_total:
                avail = len(self._free) + self._evictable \
                    - self._reserved_total
                for node in path:
                    self._decref(node)
            else:
                blocks = [self._pop_block() for _ in range(need_now)]
                shared = [n.block for n in path]
                if cow:
                    # remap the boundary to its fresh clone; the engine
                    # device-copies src -> dst before any write
                    src = shared.pop()
                    self._decref(path[-1])
                    self._cow_pending[slot] = (src, blocks[0])
                for i, b in enumerate(shared):
                    self.block_tables[slot, i] = b
                for i, b in enumerate(blocks):
                    self.block_tables[slot, len(shared) + i] = b
                self._owned[slot] = list(blocks)
                self._shared[slot] = shared
                self._tail[slot] = path[len(shared) - 1] if shared \
                    else self._root
                skip = (prompt_tokens - 1) if cow \
                    else matched * self.block_size
                self._matched[slot] = skip
                if skip:
                    self.prefix_hits += 1
                    self.prefix_tokens_reused += skip
                self._reserved[slot] = reserved
                self._reserved_total += reserved
                self._need[slot] = need
                self._lo[slot] = 0
                self._sync_gauges()
                avail = None
        if avail is not None:
            _flight.record("serving", "block_exhausted", slot=slot,
                           need=need_now + reserved, available=avail)
            return False
        _flight.record("serving", "block_alloc", slot=slot,
                       blocks=need_now, shared=matched,
                       reserved=total - now,
                       available=self.available_blocks())
        return True

    def matched_tokens(self, slot: int) -> int:
        """Prompt tokens admission matched for ``slot`` — the prefill
        may start at this offset (positions below it are already
        resident in aliased / copied blocks)."""
        return self._matched.get(int(slot), 0)

    def take_cow(self, slot: int) -> Optional[Tuple[int, int]]:
        """Pop the pending boundary copy-on-write ``(src, dst)`` pair
        recorded by ``admit`` (None when the match was not
        block-aligned). The caller MUST device-copy block ``src`` ->
        ``dst`` in every pool leaf before the slot's next write."""
        return self._cow_pending.pop(int(slot), None)

    def cow_for_write(self, slot: int, pos: int) -> \
            Optional[Tuple[int, int]]:
        """Defensive copy-on-write seam for decode/speculative writers:
        if the block covering position ``pos`` of ``slot`` is a SHARED
        prefix block, detach it — allocate a clone, remap the table,
        decref the tree node — and return ``(src, dst)`` for the
        caller's device copy. Returns None on the (universal in
        production) private-block path: admission caps matches below
        the prompt length, so every write position >= len(prompt)
        lands past the shared prefix by construction."""
        slot, pos = int(slot), int(pos)
        shared = self._shared.get(slot)
        if not shared:
            return None
        bidx = pos // self.block_size
        with self._lock:
            shared = self._shared.get(slot)
            if not shared or bidx >= len(shared):
                return None
            if bidx != len(shared) - 1:
                raise RuntimeError(
                    f"write at pos {pos} targets block {bidx} INSIDE "
                    f"slot {slot}'s shared prefix ({len(shared)} "
                    f"blocks) — only the boundary block may be "
                    f"copy-on-written; truncate the slot first")
            src = shared.pop()
            node = self._by_block[src]
            dst = self._pop_block()
            self._decref(node)
            self._tail[slot] = node.parent
            self.block_tables[slot, bidx] = dst
            self._owned.setdefault(slot, []).append(dst)
            self._sync_gauges()
        return src, dst

    def commit_prefix(self, slot: int, token_ids,
                      tokens_written: int) -> int:
        """Publish ``slot``'s fully-written prompt blocks into the
        radix tree (called after each prefill chunk, so hot prefixes
        become matchable while their first writer is still
        prefilling). Only FULL blocks whose every token is already
        written commit — a half-written block must never be aliased.
        Private blocks become tree nodes (ownership transfers, the
        slot keeps an aliased ref); a block whose key already exists
        in the tree dedupes — the slot remaps onto the cached block
        and its private copy returns to the free list. Returns the
        number of blocks committed."""
        if not self.prefix_enabled:
            return 0
        slot = int(slot)
        ids = [int(t) for t in token_ids]
        full = min(int(tokens_written), len(ids)) // self.block_size
        done = 0
        with self._lock:
            shared = self._shared.get(slot)
            owned = self._owned.get(slot)
            if shared is None or owned is None:
                return 0
            tail = self._tail.get(slot, self._root)
            for bidx in range(len(shared), full):
                key = tuple(ids[bidx * self.block_size:
                               (bidx + 1) * self.block_size])
                b = int(self.block_tables[slot, bidx])
                node = tail.children.get(key)
                if node is not None:
                    # dedupe: a concurrent writer (or this slot's own
                    # COW clone) re-created cached content — alias the
                    # tree's block, free the private duplicate
                    self._incref(node)
                    owned.remove(b)
                    self._free.append(b)
                    self.block_tables[slot, bidx] = node.block
                else:
                    if self.prefix_cap and \
                            len(self._by_block) >= self.prefix_cap:
                        freed = self._evict_one()
                        if freed is None:
                            break  # bound hit, nothing reclaimable:
                            # the suffix simply stays private
                        self._free.append(freed)
                    node = _PrefixNode(key, tail, b)
                    tail.children[key] = node
                    node.ref = 1
                    self._by_block[b] = node
                    owned.remove(b)
                shared.append(node.block)
                tail = node
                done += 1
            self._tail[slot] = tail
            if done:
                self._sync_gauges()
        return done

    def reset_prefix_cache(self) -> int:
        """Drop the whole radix tree, returning every cached block to
        the free list — the crash-recovery (`reset_state`) seam: the
        device pools are rebuilt as zeros, so cached content is no
        longer backed by real K/V. Requires every slot released first
        (a live alias would dangle). Returns the blocks reclaimed."""
        with self._lock:
            if any(n.ref for n in self._by_block.values()):
                raise RuntimeError(
                    "reset_prefix_cache with live shared blocks — "
                    "release every slot first (reset_state does)")
            n = len(self._by_block)
            self._free.extend(sorted(self._by_block, reverse=True))
            self._by_block.clear()
            self._root.children.clear()
            self._evictable = 0
            self._shared.clear()
            self._tail.clear()
            self._matched.clear()
            self._cow_pending.clear()
            self._sync_gauges()
        if n:
            _flight.record("serving", "prefix_evict", block=-1,
                           reset=True, blocks=n)
        return n

    def ensure_token(self, slot: int, pos: int) -> None:
        """Map the block covering position ``pos`` of ``slot`` if it
        is not mapped yet, drawing down the slot's admission-time
        reservation (step-boundary extension). A RuntimeError here is
        a caller bug: the budget passed to ``admit`` was too small."""
        slot, pos = int(slot), int(pos)
        bidx = pos // self.block_size
        if bidx >= self.max_blocks_per_slot:
            raise ValueError(
                f"position {pos} is past the cache capacity "
                f"({self.max_blocks_per_slot * self.block_size} tokens)")
        if self.window is not None:
            self.advance(slot, pos, pos)
            return
        if self.block_tables[slot, bidx] >= 0:
            return
        with self._lock:
            if self.block_tables[slot, bidx] >= 0:
                return  # raced: another thread mapped it first — a
                # double-pop here would orphan a block AND over-draw
                # the reservation (the check above is lock-free)
            if self._reserved.get(slot, 0) <= 0:
                raise RuntimeError(
                    f"slot {slot} has no KV reservation left at pos "
                    f"{pos} — the generation budget passed at admission "
                    f"was too small")
            b = self._pop_block()
            self._reserved[slot] -= 1
            self._reserved_total -= 1
            self._owned[slot].append(b)
            self.block_tables[slot, bidx] = b
            self._sync_gauges()
        _flight.record("serving", "block_alloc", slot=slot, blocks=1,
                       block_index=bidx,
                       available=self.available_blocks())

    def advance(self, slot: int, first_pos: int, last_pos: int) -> int:
        """Move ``slot``'s live range on to the rows ``[first_pos,
        last_pos]`` that the next launch writes: map the blocks through
        ``last_pos`` and, in a window table, first give back every
        block that lies wholly behind ``first_pos - window + 1`` (no row
        of this launch or a later one can see it). A freed block is
        re-credited to the slot's reservation as far as the request
        still has blocks ahead, so what a slot holds and may still draw
        never passes ``hold_blocks`` and a draw here cannot fail. A
        full table maps only (its prompt blocks were mapped at
        admission). Returns the blocks freed."""
        slot = int(slot)
        hi = min(int(last_pos) // self.block_size,
                 self.max_blocks_per_slot - 1)
        if self.window is None:
            for bidx in range(int(first_pos) // self.block_size, hi + 1):
                if self.block_tables[slot, bidx] < 0:
                    self.ensure_token(slot, bidx * self.block_size)
            return 0
        lo = max(int(first_pos) - self.window + 1, 0) // self.block_size
        freed = mapped = 0
        with self._lock:
            owned = self._owned.get(slot)
            if owned is None:
                raise RuntimeError(f"slot {slot} holds no KV blocks")
            for bidx in range(self._lo.get(slot, 0), min(lo, hi + 1)):
                b = int(self.block_tables[slot, bidx])
                if b >= 0:
                    self.block_tables[slot, bidx] = -1
                    owned.remove(b)
                    self._free.append(b)
                    freed += 1
            lo = max(self._lo.get(slot, 0), min(lo, hi))
            self._lo[slot] = lo
            if freed:
                hold = min(self.hold_blocks, self._need[slot] - lo)
                credit = max(hold - len(owned), 0) - self._reserved[slot]
                self._reserved[slot] += credit
                self._reserved_total += credit
            for bidx in range(lo, hi + 1):
                if self.block_tables[slot, bidx] >= 0:
                    continue
                if self._reserved.get(slot, 0) <= 0:
                    raise RuntimeError(
                        f"slot {slot} has no KV reservation left mapping "
                        f"block {bidx} of its window table (live from "
                        f"{lo}, {len(owned)} held of {self.hold_blocks}): "
                        f"the budget passed at admission was too small, "
                        f"or a launch writes more rows than the table's "
                        f"window_slack")
                b = self._pop_block()
                self._reserved[slot] -= 1
                self._reserved_total -= 1
                owned.append(b)
                self.block_tables[slot, bidx] = b
                mapped += 1
            if freed or mapped:
                self._sync_gauges()
        if freed:
            _M_window_freed.inc(freed)
        if freed or mapped:
            _flight.record("serving", "window_advance", slot=slot,
                           freed=freed, mapped=mapped, first_block=lo,
                           available=self.available_blocks())
        return freed

    def reserve_through(self, slot: int, pos: int) -> None:
        """Materialize every block covering positions [0, pos] — the
        decode-window pre-extension (``decode_steps`` needs a block
        table that stays valid for the whole device-resident loop)."""
        if self.window is not None:
            raise NotImplementedError(
                "a window table maps one launch ahead (advance); a "
                "device-resident decode window is not built for it")
        last = min(int(pos) // self.block_size,
                   self.max_blocks_per_slot - 1)
        for bidx in range(last + 1):
            if self.block_tables[int(slot), bidx] < 0:
                self.ensure_token(slot, bidx * self.block_size)

    def truncate(self, slot: int, tokens: int) -> int:
        """Roll back ``slot``'s mapping to its first ``tokens``
        positions: blocks past the last kept position are returned to
        the free list and RE-CREDITED to the slot's reservation — the
        speculative-decode rollback seam (a rejected draft's tokens
        are just extra block writes; un-mapping them restores the
        admission-time budget so the next window's pre-extension can
        draw the same blocks again). Returns the block count rolled
        back."""
        if self.window is not None:
            raise NotImplementedError(
                "rolling back a window table is not built: a block "
                "freed behind the window cannot be had again")
        slot, tokens = int(slot), int(tokens)
        keep = _ceil_div(tokens, self.block_size) if tokens > 0 else 0
        rolled = unshared = 0
        with self._lock:
            owned = self._owned.get(slot)
            if owned is None:
                return 0
            shared = self._shared.get(slot, [])
            if keep < len(shared):
                # rolling back INTO the shared prefix (never the spec
                # path — committed streams cover the whole prompt —
                # but direct truncate may): decref, don't free, and do
                # NOT re-credit the reservation (aliased blocks were
                # never charged against it)
                for b in shared[keep:]:
                    self._decref(self._by_block[b])
                    unshared += 1
                self.block_tables[slot, keep:len(shared)] = -1
                del shared[keep:]
                tail = self._root
                for b in shared:
                    tail = self._by_block[b]
                self._tail[slot] = tail
                self._matched[slot] = min(
                    self._matched.get(slot, 0),
                    keep * self.block_size)
            for bidx in range(max(keep, len(shared)),
                              self.max_blocks_per_slot):
                b = int(self.block_tables[slot, bidx])
                if b < 0:
                    continue
                self.block_tables[slot, bidx] = -1
                owned.remove(b)
                self._free.append(b)
                rolled += 1
            if rolled:
                # invariant preserved: free and reserved_total grow by
                # the same amount, so free >= reserved_total still holds
                self._reserved[slot] = self._reserved.get(slot, 0) \
                    + rolled
                self._reserved_total += rolled
            if rolled or unshared:
                self._sync_gauges()
        if rolled or unshared:
            _flight.record("serving", "block_rollback", slot=slot,
                           blocks=rolled, unshared=unshared,
                           kept_tokens=tokens,
                           available=self.available_blocks())
        return rolled

    def release(self, slot: int, evicted: bool = False) -> int:
        """Return all of ``slot``'s private blocks, decref its shared
        prefix (the tree KEEPS those blocks cached at ref 0, where
        they stay matchable until LRU pressure reclaims them) and
        cancel its reservation. ``evicted=True`` marks a reclaim
        (deadline expiry, failure, cancellation) and bumps
        ``serving.block_evictions_total`` for the private blocks;
        normal completion leaves the counter alone."""
        slot = int(slot)
        with self._lock:
            blocks = self._owned.pop(slot, [])
            shared = self._shared.pop(slot, [])
            for b in shared:
                self._decref(self._by_block[b])
            self._tail.pop(slot, None)
            self._matched.pop(slot, None)
            self._cow_pending.pop(slot, None)
            resv = self._reserved.pop(slot, 0)
            self._reserved_total -= resv
            self._need.pop(slot, None)
            self._lo.pop(slot, None)
            self._free.extend(blocks)
            self.block_tables[slot, :] = -1
            if evicted and blocks:
                self.evictions += len(blocks)
            self._sync_gauges()
        if evicted and blocks:
            _M_evictions.inc(len(blocks))
        if blocks or shared or resv:
            _flight.record("serving", "block_free", slot=slot,
                           blocks=len(blocks), unshared=len(shared),
                           evicted=bool(evicted),
                           available=self.available_blocks())
        return len(blocks)

    def check_invariants(self) -> None:
        """Assert the allocator's global invariants (the tests'
        step-boundary probe; not on any hot path):

        - free / privately-owned / tree blocks PARTITION the pool;
        - every node's refcount equals the number of slots aliasing
          its block, and never exceeds its parent's;
        - the evictable count equals the ref-0 node count;
        - each slot's shared blocks are a contiguous table prefix;
        - ``free + evictable - reserved_total >= 0`` (reservations
          can always be honored without touching a live block).
        """
        with self._lock:
            free = list(self._free)
            owned_all = [b for bs in self._owned.values() for b in bs]
            tree = list(self._by_block)
            assert len(set(free)) == len(free), "free-list duplicates"
            assert len(set(owned_all)) == len(owned_all), \
                "block owned by two slots"
            union = free + owned_all + tree
            assert sorted(union) == list(range(self.num_blocks)), (
                f"pool partition broken: free={sorted(free)} "
                f"owned={sorted(owned_all)} tree={sorted(tree)}")
            want_ref: Dict[int, int] = {}
            for slot, shared in self._shared.items():
                for i, b in enumerate(shared):
                    assert int(self.block_tables[slot, i]) == b, \
                        f"slot {slot} shared prefix not contiguous"
                    want_ref[b] = want_ref.get(b, 0) + 1
            zero = 0
            for b, node in self._by_block.items():
                assert node.block == b
                assert node.ref == want_ref.get(b, 0), (
                    f"block {b}: ref {node.ref} != "
                    f"{want_ref.get(b, 0)} aliasing slots")
                assert node.parent is self._root \
                    or node.parent.ref >= node.ref, \
                    f"block {b}: child outrefs its parent"
                zero += node.ref == 0
            assert zero == self._evictable, \
                f"evictable count {self._evictable} != {zero} ref-0 nodes"
            assert self._reserved_total == sum(self._reserved.values())
            assert len(free) + zero - self._reserved_total >= 0, (
                f"reservation invariant broken: free={len(free)} "
                f"evictable={zero} reserved={self._reserved_total}")

    def active_tokens(self, pos: np.ndarray,
                      active: np.ndarray) -> int:
        """Tokens currently resident across active slots (the paged
        roofline's cache-traffic term: O(active tokens), not
        O(slots x max_seq))."""
        return int(sum(int(p) for p, a in zip(pos, active) if a))


class KindedKVCache:
    """A block table and an allocator per KIND of layer, for a model
    whose layers do not all keep the same history: ``full`` layers keep
    every position, ``window`` layers the last ``W``. One
    :class:`PagedKVCache` a kind (each with its own pool size; the
    layers of a kind share its table, each with a pool of its own), and
    the calls an engine makes go to every kind: admission reserves by
    kind (the whole need in the full table, at most ``hold_blocks`` in
    a window table) and takes all or nothing, ``advance`` frees behind
    the windows, ``release`` returns every kind's blocks.

    Prefix sharing is off here and asking for it is an error (see
    ``PagedKVCache``'s window note); rolling back (speculation) is not
    built for window tables."""

    def __init__(self, max_slots: int, max_seq: int, block_size: int,
                 kinds: Dict[str, dict],
                 prefix_cache: Optional[bool] = None):
        if prefix_cache:
            raise ValueError(
                "prefix sharing is not supported for a model with "
                "window layers: a window layer frees its blocks behind "
                "the window, a shared prefix must not be freed")
        self.block_size = int(block_size)
        self.kinds: Dict[str, PagedKVCache] = {
            kind: PagedKVCache(
                max_slots, max_seq, block_size, spec.get("num_blocks"),
                prefix_cache=False, window=spec.get("window"),
                window_slack=spec.get("window_slack", 0), kind=kind)
            for kind, spec in kinds.items()}
        self.max_blocks_per_slot = _ceil_div(max_seq, self.block_size)
        self.prefix_enabled = False

    @property
    def block_tables(self) -> Dict[str, np.ndarray]:
        return {k: c.block_tables for k, c in self.kinds.items()}

    def _tightest(self) -> PagedKVCache:
        return min(self.kinds.values(),
                   key=lambda c: c.available_blocks() / c.num_blocks)

    @property
    def num_blocks(self) -> int:
        """Of the kind nearest exhaustion (what admission pressure,
        ``available_blocks() / num_blocks``, is read from)."""
        return self._tightest().num_blocks

    def available_blocks(self) -> int:
        return self._tightest().available_blocks()

    def used_blocks(self) -> int:
        return sum(c.used_blocks() for c in self.kinds.values())

    def occupied_slots(self) -> int:
        return max(c.occupied_slots() for c in self.kinds.values())

    def admit(self, slot: int, prompt_tokens: int, total_tokens: int,
              token_ids=None) -> bool:
        done = []
        for c in self.kinds.values():
            try:
                ok = c.admit(slot, prompt_tokens, total_tokens)
            except Exception:
                for d in done:
                    d.release(slot)
                raise
            if not ok:
                for d in done:
                    d.release(slot)
                return False
            done.append(c)
        return True

    def advance(self, slot: int, first_pos: int, last_pos: int) -> int:
        return sum(c.advance(slot, first_pos, last_pos)
                   for c in self.kinds.values())

    def ensure_token(self, slot: int, pos: int) -> None:
        for c in self.kinds.values():
            c.ensure_token(slot, pos)

    def release(self, slot: int, evicted: bool = False) -> int:
        return sum(c.release(slot, evicted=evicted)
                   for c in self.kinds.values())

    def reserve_through(self, slot: int, pos: int) -> None:
        for c in self.kinds.values():
            c.reserve_through(slot, pos)

    def truncate(self, slot: int, tokens: int) -> int:
        return sum(c.truncate(slot, tokens) for c in self.kinds.values())

    # prefix sharing is off: the engine's calls find nothing shared
    def matched_tokens(self, slot: int) -> int:
        return 0

    def take_cow(self, slot: int):
        return None

    def cow_for_write(self, slot: int, pos: int):
        return None

    def commit_prefix(self, slot: int, token_ids, tokens_written: int) -> int:
        return 0

    def reset_prefix_cache(self) -> int:
        return 0

    def check_invariants(self) -> None:
        for c in self.kinds.values():
            c.check_invariants()

    def stats(self) -> Dict[str, object]:
        """The tightest kind's numbers under the one-table keys, every
        kind's own under ``kinds``."""
        out: Dict[str, object] = dict(self._tightest().stats())
        out["kinds"] = {k: c.stats() for k, c in self.kinds.items()}
        return out

    def active_tokens(self, pos: np.ndarray, active: np.ndarray) -> int:
        return next(iter(self.kinds.values())).active_tokens(pos, active)


class SlotStates:
    """The host side of a model whose every layer keeps a state a SLOT and
    no block pool: no block table, no allocator. A request is admitted by
    its slot alone (the state pool holds every slot's state from boot), so
    admission never waits for memory and a request's length is bounded by
    positions, not blocks. It answers the calls an engine makes of a block
    cache as a cache with no blocks: nothing to map, reserve, roll back or
    share."""

    block_tables = None            # nothing for a launch to upload
    num_blocks = 0
    prefix_enabled = False

    def __init__(self):
        self._held: set = set()
        self.evictions = 0

    def available_blocks(self) -> int:
        return 0

    def used_blocks(self) -> int:
        return 0

    def admit(self, slot: int, prompt_tokens: int, total_tokens: int,
              token_ids=None) -> bool:
        slot = int(slot)
        if slot in self._held:
            raise ValueError(f"slot {slot} already holds a request")
        self._held.add(slot)
        _flight.record("serving", "slot_admit", slot=slot,
                       tokens=int(total_tokens))
        return True

    def release(self, slot: int, evicted: bool = False) -> int:
        slot = int(slot)
        if slot in self._held:
            self._held.discard(slot)
            self.evictions += bool(evicted)
            _flight.record("serving", "slot_free", slot=slot,
                           evicted=bool(evicted))
        return 0

    # nothing is reserved ahead of a write or shared
    def reserve_through(self, slot: int, pos: int) -> None:
        return None

    def matched_tokens(self, slot: int) -> int:
        return 0

    def take_cow(self, slot: int):
        return None

    def cow_for_write(self, slot: int, pos: int):
        return None

    def commit_prefix(self, slot: int, token_ids, tokens_written: int) -> int:
        return 0

    def reset_prefix_cache(self) -> int:
        return 0

    def stats(self) -> Dict[str, int]:
        """No blocks: the slots that hold a request and the evictions (the
        engine's ``state_stats`` adds the state pool's slots and bytes)."""
        return {"num_blocks": 0, "blocks_used": 0, "blocks_reserved": 0,
                "slots_held": len(self._held), "evictions": self.evictions}


# ---------------------------------------------------------------------------
# device side: quantized block writes + tiled streaming attention
# ---------------------------------------------------------------------------

def set_pool_gauges(blocks: Dict[str, int]) -> None:
    """``serving.kv_blocks_in_use{pool}`` from an engine's count of
    mapped blocks by pool name (``pool_blocks_in_use``)."""
    for name, n in blocks.items():
        _G_kind_blocks.set(n, pool=name)


def absmax_quantize(x, bits: int = 8):
    """Symmetric per-(token, head) absmax int8 of K/V rows
    ``[N, KVH, D]`` -> ``(codes int8 [N, KVH, D], scale f32 [N, KVH])``
    (the caller flattens the codes to the pool's ``[N, KVH*D]`` rows)
    — the ``quantization.quantize.quant_absmax`` step computation
    (dynamic absmax over the head dim, qmax = 2^(bits-1) - 1), kept
    raw-code-valued here because the pool STORES the codes and the
    attention tiles dequantize on gather."""
    qmax = float(2 ** (bits - 1) - 1)
    a = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=-1), 1e-8) / qmax
    codes = jnp.clip(jnp.round(a / scale[..., None]),
                     -qmax, qmax).astype(jnp.int8)
    return codes, scale


def copy_block(pool, src, dst):
    """Device-copy one whole physical block (all ``block_size`` rows)
    ``pool[src] -> pool[dst]`` — the copy-on-write data move, riding
    the same scatter seam as :func:`write_kv_tokens` (an ``.at[]``
    update the engine runs with the pool donated, so the copy lands in
    place in HBM)."""
    return pool.at[dst].set(pool[src])


def write_kv_tokens(pool, phys, off, vals):
    """Scatter ``vals [N, ...]`` into ``pool[phys[i], off[i]]`` cells;
    rows whose ``phys`` is out of range (the caller maps invalid rows
    to ``num_blocks``) are dropped, so padded prefill rows and
    inactive decode slots never touch a real block."""
    return pool.at[phys, off].set(vals.astype(pool.dtype), mode="drop")


# [T, H, D] f32 bytes above which the Pallas kernel's per-program
# VMEM working set (acc scratch + q/out tiles, each T*H*D*4) risks the
# ~16 MB/core budget — such calls fall back to the jnp walk
_KERNEL_Q_VMEM_BUDGET = 4 * 1024 * 1024


def use_kernel_default(head_dim: int) -> bool:
    """The seam's path decision, from what it can observe: the Pallas
    block-table kernel where the backend and the head width support it
    (``ops.pallas.paged_attention.kernel_available``: a TPU, heads of
    whole 128-lane rows); the pure-jnp tiled walk (the numerics oracle)
    otherwise. One function so engines can count the live path per step
    without re-deriving the policy."""
    from .ops.pallas import paged_attention as _pk
    return _pk.kernel_available(head_dim)


def _kernel_row_tile(T: int, H: int, D: int) -> int:
    """Rows a slot the Pallas kernel takes at once: all ``T`` where its
    float32 accumulator fits ``_KERNEL_Q_VMEM_BUDGET``, else the largest
    divisor of ``T`` that does (0 if none: the caller takes the walk)."""
    fit = _KERNEL_Q_VMEM_BUDGET // (H * D * 4)
    return next((t for t in range(min(T, fit), 0, -1) if T % t == 0), 0)


def paged_attention(q, k_pool, v_pool, tables, positions, *,
                    block_size: int, n_rep: int, n_tiles=None,
                    k_scale=None, v_scale=None, use_kernel=None,
                    lower=None):
    """Block-table-gathered streaming attention for one layer.

    ``q [S, T, H, D]`` attends to the K/V history of its slot, stored
    as pool blocks ``[num_blocks, block_size, KVH*D]`` (``KVH = H //
    n_rep``; the one layout this seam takes) addressed through
    ``tables [S, max_blocks]`` (entry < 0 = unmapped). The heads are
    flat because that is what the kernel's block copy reads: compiled
    for the v5e a bf16 ``[..., KVH, 128]`` array is tiled
    ``T(4,128)(2,1)`` and the kernel's ``[bs, KVH*D]`` slab
    ``T(8,128)(2,1)``, so a four-dimensional pool cost a copy of the
    whole pool, K and V, a layer a launch. Row
    ``(s, t)`` may attend every column ``c <= positions[s, t]`` and,
    with ``lower [S, T]`` (a window layer: ``positions - W + 1``), only
    ``c >= lower[s, t]``; the walk then starts at the first block any
    row still sees, so blocks freed behind the window are never read.

    The walk is an online-softmax loop over ``block_size`` tiles
    (``jax.lax.fori_loop``, so ``n_tiles`` — typically
    ``max(positions)//block_size + 1`` — may be a traced value and
    short sequences pay only their own tiles): per tile it gathers one
    block per slot, forms ``[S, ., T, block_size]`` scores, and folds
    them into running (max, denominator, accumulator) carries. No
    ``[S, max_seq]`` score or cache view ever exists — peak extra
    memory is one tile, which is what lets a Pallas TPU kernel replace
    this function behind the same signature.

    GQA runs against the UNEXPANDED pools (grouped contraction):
    ``n_rep = H // KVH`` query heads share each
    KV head. ``k_scale/v_scale [num_blocks, block_size, KVH]`` switch
    the gather to int8-dequant mode (absmax codes in the pools).

    ``use_kernel`` selects the implementation behind this ONE seam:
    None (default) follows backend and head-width availability
    (``use_kernel_default``), True forces the Pallas TPU kernel
    (``ops.pallas.paged_attention``), False forces the jnp walk below
    — which stays the numerics ORACLE the kernel is parity-pinned
    against (tests/test_serving_spec.py runs the kernel through the
    Pallas interpreter on CPU and asserts same-numerics).
    """
    S, T, H, D = q.shape
    if k_pool.shape[1:] != (block_size, H // n_rep * D):
        raise ValueError(
            f"a KV pool is [num_blocks, block_size, KVH*D] = [., "
            f"{block_size}, {H // n_rep * D}] here, got {k_pool.shape}")
    if use_kernel is None:
        use_kernel = use_kernel_default(q.shape[3])
    row_tile = q.shape[1]
    if use_kernel and q.shape[1] * q.shape[2] * q.shape[3] * 4 \
            > _KERNEL_Q_VMEM_BUDGET:
        # the kernel's f32 accumulator scratch (and its q/out tiles)
        # scale with T*H*D: decode (T=1), spec verify (T=k+1) and a
        # 64-row chunk of 32 heads fit; a wider chunk (512 rows of 128
        # heads) goes to the kernel a tile of rows at a time, each tile
        # a slot of its own over the same table row. Only a row count
        # with no divisor that fits takes the jnp walk, same numerics
        row_tile = _kernel_row_tile(*q.shape[1:])
        use_kernel = row_tile >= 8 or row_tile == q.shape[1]
    if use_kernel and block_size * k_pool.dtype.itemsize < 4:
        # the kernel copies one block at a time, and Mosaic copies no
        # slab thinner than a 32-bit sublane row (block_size 1 in
        # bf16, 1-2 in int8: no engine's default): same numerics
        use_kernel = False
    from .ops.pallas import count_path
    count_path("paged_attention", "pallas" if use_kernel else "jnp_walk")
    if use_kernel:
        from .ops.pallas import paged_attention as _pk
        nt = T // row_tile

        def rows(a):
            return None if a is None else a.reshape(
                (S * nt, row_tile) + a.shape[2:])
        out = _pk.paged_attention_kernel(
            rows(q), k_pool, v_pool,
            tables if nt == 1 else jnp.repeat(tables, nt, axis=0),
            rows(positions), block_size=block_size, n_rep=n_rep,
            n_tiles=n_tiles, k_scale=k_scale, v_scale=v_scale,
            lower=rows(lower))
        return out.reshape(S, T, H, D)
    R = int(n_rep)
    K = H // R
    if n_tiles is None:
        n_tiles = tables.shape[1]
    first_tile = 0
    if lower is not None:
        lower = jnp.maximum(lower, 0)
        first_tile = jnp.minimum(jnp.min(lower) // block_size, n_tiles)
    q5 = q.reshape(S, T, K, R, D)
    inv_sqrt_d = 1.0 / np.sqrt(D)
    cols0 = jnp.arange(block_size)
    m0 = jnp.full((S, K, R, T), -1e30, jnp.float32)
    l0 = jnp.zeros((S, K, R, T), jnp.float32)
    a0 = jnp.zeros((S, K, R, T, D), jnp.float32)

    def tile(i, carry):
        m, l, acc = carry
        phys = jnp.maximum(tables[:, i], 0)            # [S]
        # the heads are split on the gathered tile, never on a pool
        k_t = k_pool[phys].reshape(S, block_size, K, D)
        v_t = v_pool[phys].reshape(S, block_size, K, D)
        if k_scale is not None:
            k_t = (k_t.astype(jnp.float32)
                   * k_scale[phys][..., None]).astype(q.dtype)
            v_t = (v_t.astype(jnp.float32)
                   * v_scale[phys][..., None]).astype(q.dtype)
        # RECYCLED blocks may hold non-finite garbage from a previous
        # request (a pathological prompt can drive activations to
        # NaN/inf). Masked columns must contribute EXACTLY zero, but
        # 0 * NaN = NaN in the PV contraction below — sanitize the
        # gathered tile so one request's garbage can never leak into
        # another request sharing the pool
        k_t = jnp.nan_to_num(k_t)
        v_t = jnp.nan_to_num(v_t)
        s = jnp.einsum("stkrd,sbkd->skrtb", q5, k_t,
                       preferred_element_type=jnp.float32) * inv_sqrt_d
        # [S, T, bs] -> broadcast over (K, R); also masks unmapped
        # blocks (cols of tile i all exceed positions that never
        # reached it) and clamped phys-0 garbage for inactive slots
        cols = (i * block_size + cols0)[None, None, :]
        ok = cols <= positions[:, :, None]
        if lower is not None:
            ok = ok & (cols >= lower[:, :, None])
        okb = ok[:, None, None, :, :]
        s = jnp.where(okb, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a fully-masked row has s == m_new == -1e30: exp() gives 1,
        # so re-mask p to zero its contribution exactly
        p = jnp.where(okb, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("skrtb,sbkd->skrtd", p.astype(v_t.dtype), v_t,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(first_tile, n_tiles, tile, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(S, T, H, D).astype(
        q.dtype)


# ---------------------------------------------------------------------------
# learned sparse attention over a latent pool: index scores over a paged
# key pool, the top-k of each row, attention over the selected rows only
# ---------------------------------------------------------------------------

_SELECT_BITS = 2         # bits of the threshold a pass of the search fixes


def _ordered_u32(x):
    """float32 -> uint32, monotone: a larger float is a larger integer
    (``-0.0`` first made ``0.0``, so equal floats are equal integers)."""
    x = jnp.where(x == 0, 0.0, x).astype(jnp.float32)
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def select_topk(scores, valid, k: int):
    """The selection rule of learned sparse attention, defined here once.

    ``scores [..., N]`` float32, ``valid [..., N]`` bool. A row's selected
    set is its ``k`` largest valid scores, a tie at the ``k``-th value
    going to the LOWER position; every valid position where fewer than
    ``k`` are valid. Returns ``(selected [..., N] bool, n_sel [...]
    int32)``. Exact, and free of a sort: the ``k``-th largest value is
    found by a search over the bits of the float (``_SELECT_BITS`` a pass,
    each pass one counting read of the row: at 2 bits 16 reads and 3
    compares an element, against 8 and 15 at 4 bits, which the v5e's VPU
    took 0.69 ms a pass for at ``[512, 51200]``); the positions above it
    and the first ties at it are kept, the ties ranked only where some
    row has more of them than it has room for."""
    k = int(k)
    lead = scores.shape[:-1]
    u = jnp.where(valid, _ordered_u32(scores), jnp.uint32(0))
    prefix = jnp.zeros(lead, jnp.uint32)
    steps = jnp.arange(1, 1 << _SELECT_BITS, dtype=jnp.uint32)
    for shift in range(32 - _SELECT_BITS, -1, -_SELECT_BITS):
        cands = prefix[..., None] | (steps << shift)   # [..., 2**bits - 1]
        above = jnp.sum(u[..., None, :] >= cands[..., :, None], axis=-1,
                        dtype=jnp.int32)
        # counts fall as the candidate grows: those with k or more at or
        # above them are a prefix of the candidates
        prefix = prefix | (jnp.sum(above >= k, axis=-1).astype(jnp.uint32)
                           << shift)
    # prefix: the k-th largest value (0 where fewer than k are valid)
    gt = u > prefix[..., None]
    eq = (u == prefix[..., None]) & valid
    room = k - jnp.sum(gt, axis=-1, dtype=jnp.int32)
    crowded = jnp.any(jnp.sum(eq, axis=-1, dtype=jnp.int32) > room)
    sel = jax.lax.cond(
        crowded,
        lambda: gt | (eq & (jnp.cumsum(eq, axis=-1, dtype=jnp.int32)
                            <= room[..., None])),
        lambda: gt | eq)
    return sel, jnp.sum(sel, axis=-1, dtype=jnp.int32)


def positions_bitset(selected):
    """``[..., N]`` bool -> ``[..., ceil(N / 32)]`` uint32: position ``p`` is
    bit ``p % 32`` of word ``p // 32``. What a launch hands back of a
    selection (a 51,200-position row in 6.4 KB); ``bitset_positions`` reads
    it on the host."""
    n = selected.shape[-1]
    words = -(-n // 32)
    bits = jnp.pad(selected, [(0, 0)] * (selected.ndim - 1)
                   + [(0, words * 32 - n)])
    bits = bits.reshape(selected.shape[:-1] + (words, 32)).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def bitset_positions(words) -> np.ndarray:
    """The positions set in one row of ``positions_bitset`` (host side)."""
    words = np.ascontiguousarray(np.asarray(words), dtype="<u4")
    return np.flatnonzero(np.unpackbits(words.view(np.uint8),
                                        bitorder="little"))


def write_rows(pools, rows, positions, tables, wmask, block_size: int):
    """Scatter one row a token into each named pool: ``pools {name:
    [num_blocks, block_size, width]}``, ``rows {name: [S, T, width]}`` at
    ``positions [S, T]`` through ``tables [S, max_blocks]``. Rows with
    ``wmask`` False or an unmapped table entry are dropped (an index past
    the pool), so padding and inactive slots never touch a real block.
    Returns the pools named in ``rows``, written."""
    S, T = positions.shape
    bidx = jnp.minimum(positions // block_size, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables, bidx, axis=1)
    ok = jnp.logical_and(wmask, phys >= 0)
    off = (positions % block_size).reshape(-1)
    out = {}
    for name, vals in rows.items():
        pool = pools[name]
        p = jnp.where(ok, phys, pool.shape[0]).reshape(-1)
        out[name] = write_kv_tokens(pool, p, off, vals.reshape(S * T, -1))
    return out


def paged_index_scores(q, w, k_pool, tables, positions, *, block_size: int,
                       use_kernel=None):
    """Indexer scores of each query row over its slot's paged key pool.

    ``q [S, T, J, D]``, ``w [S, T, J]`` (float32 head weights), ``k_pool
    [num_blocks, block_size, D]``, ``tables [S, max_blocks]``, ``positions
    [S, T]``. Returns ``(scores [S, T, N] float32, valid [S, T, N])`` with
    ``N >= max_blocks * block_size`` (whole key tiles): ``scores[s, t, c]
    = sum_j w_j relu(q_j . k_c)`` and ``valid`` where ``c <= positions[s,
    t]`` lies in a mapped block. Scored by
    ``ops.pallas.sparse_latent.index_scores``, which reads the keys where
    they lie, through the table: a slot's blocks up to its last row."""
    from .ops.pallas import sparse_latent as _sl
    scores = _sl.index_scores(q, w, k_pool, tables, positions,
                              block_size=block_size, use_kernel=use_kernel)
    N = scores.shape[-1]
    mapped = jnp.pad(tables >= 0, ((0, 0), (0, N // block_size
                                             - tables.shape[1])))
    mapped = jnp.repeat(mapped, block_size, axis=1)              # [S, N]
    valid = (jnp.arange(N)[None, None, :] <= positions[:, :, None]) \
        & mapped[:, None, :]
    return scores, valid


def paged_latent_attention(q, pool, tables, selected, positions, *,
                           block_size: int, rank: int, scale: float,
                           use_kernel=None):
    """Absorbed latent attention over the SELECTED rows of a paged pool.

    ``q [S, T, H, W]`` (each head's query folded through the key
    up-projection, then its rope part, zero-padded to ``W``), ``pool
    [num_blocks, block_size, W]`` rows ``[c_kv ; k_rope ; 0]``, ``selected
    [S, T, N]`` the positions each row attends (``select_topk``). Returns
    ``[S, T, H, rank]``: the softmax runs over the selected positions and
    no other; a slot's blocks are walked through its table row under that
    mask (``ops.pallas.sparse_latent.latent_attention``, which says why a
    walk and not a gather)."""
    from .ops.pallas import sparse_latent as _sl
    return _sl.latent_attention(q, pool, tables, selected, positions,
                                block_size=block_size, rank=rank, scale=scale,
                                use_kernel=use_kernel)
