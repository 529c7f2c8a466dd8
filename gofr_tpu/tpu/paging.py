"""The engine: a page pool, block tables and the step programs, on
engine.py's serving loop.

`PagedLLMEngine` is the one engine every caller constructs. engine.py
(`LLMEngine`) is its loop half: queue, admission heap, pipelined dispatch,
sync / demux / emit, failure handling. This file is the device half: what
the chip holds and every program that runs on it (SURVEY.md §5
long-context row):

  - K/V live in a FIXED pool [L, P, Hkv, dh, page_size] allocated once at
    boot — no growth copies, no per-slot max_seq reservation
  - a slot owns ceil((prompt + max_new) / page_size) pages, mapped by a
    block table; pages return to the free list the moment the slot finishes
  - admission defers (FIFO) when the free list cannot cover a request, so
    the pool is an explicit budget instead of an OOM surprise
  - decode reads ride the scalar-prefetch Pallas kernel
    (ops/paged_attention): the block table rides in SMEM and picks which
    HBM pages the kernel copies in, a row's live pages and no others —
    per-step traffic and time track live pages, and the pallas operands
    keep the pool in its unpadded S-minor layout
  - decode writes are one flush a block: a decode program's new K and V
    wait in a tail beside the pool and each live row's page is rewritten
    once when the block's steps are over (ops/paged_attention), so the
    pool holds every token at every program boundary and nothing outside
    the program ever sees the tail
  - the block table is host-owned (plain numpy) and uploaded per dispatch,
    bucketed to power-of-two widths to bound compiled decode variants
  - a family whose blocks differ in what they keep says PAGE GROUPS
    (models/protocol.py `groups`): an allocator, a table and a reservation
    a group, a pool a plane a group. A group without a window is what the
    lines above say. A WINDOW group's blocks attend the last W tokens
    only: a sequence reserves min(pages_for(prompt + max_new), ring)
    pages there at admission and frees them at finish like the others
    (nothing is released in flight), the token at position p lives in ring
    column (p // page_size) % ring, the prefill writer puts the prompt's
    last ring of pages only, and the read is told a lower bound a row. Its
    pool is sized for every slot's whole ring, so admission never waits on
    it: `allocator` stays the first unbounded group's, the one that fills

The allocator is the HBM analog of the reference's connection-pool
bookkeeping (sql.go pool stats): a resource ledger the serving loop
consults before committing work.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..models.llama import LlamaConfig, llama_prefill_last
from ..ops.paged_attention import (column_tail, flush_columns, flush_planes,
                                   fold_branch, fold_of, fold_widths,
                                   group_of, holds_request,
                                   paged_write_columns,
                                   paged_write_prefill_scales,
                                   paged_write_prefill_stacked,
                                   paged_write_window, plane_tail,
                                   quantize_kv)
from .engine import (CacheLostError, GenerationRequest, LLMEngine,
                     LookupCount, _admission_widths, _pin_standard_layout,
                     program_lookup)
from .ownership import loop_only


class PageAllocator:
    """Free-list page ledger. Page ids run [0, n_pages); page 0 is reserved
    as the GARBAGE page and never handed out. Garbage-at-zero is a safety
    invariant, not a convenience: zero-filled block-table entries (inactive
    slot rows, dead columns) then point at garbage BY CONSTRUCTION, so a
    lock-step decode's junk writes for inactive/overrun rows can never land
    in a live page."""

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (1 usable + garbage)")
        self.n_pages = n_pages
        self.page_size = page_size
        self.garbage_page = 0
        self._free: List[int] = list(range(1, n_pages))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    def pages_for(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.page_size))

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages or None (never partial)."""
        if n > len(self._free):
            return None
        taken, self._free = self._free[:n], self._free[n:]
        return taken

    def release(self, pages: Sequence[int]) -> None:
        self._free.extend(pages)


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _shaped(shape, dtype=np.int32):
    """An argument a program lookup describes and does not hold: shape and
    dtype, no array. A lookup makes nothing on the device, so it waits
    for nothing queued there; a miss lowers from these as it would from
    uncommitted arrays of zeros (`Executor.compile` reads shape and dtype,
    and no device, of a leaf without a sharding)."""
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _folds(pages, c: int):
    """(folds, folds computed under `c` pages wide, pages computed) of
    reads that walk `pages` pages each (an int array) in folds of `c`, as
    the read's kernel runs them (ops/paged_attention `_paged_kernel`):
    every fold of a read but its last holds `c` pages and is computed at
    `c`; the last at `fold_widths(c)[fold_branch(what it holds, c)]`."""
    folds, walked = -(-pages // c), pages > 0
    last = np.take(fold_widths(c),
                   fold_branch(pages - (folds - 1) * c, c)) * walked
    return (int(folds.sum()), int(((last < c) & walked).sum()),
            int(((folds - 1) * c * walked + last).sum()))


def _groups(pages, held, c: int, r: int):
    """(row reads that took the short rows' step, row reads, groups that
    walked row by row, groups read) of reads whose rows, ALL the kernel's
    in their order, walk `pages` pages each ([rows, reads] ints; `held`
    [rows]: which hold a request), `r` consecutive rows a grid step in
    folds of `c`, as the read's kernel runs them (ops/paged_attention
    `_paged_kernel`): a group takes the short rows' step where each of its
    rows holds one fold at most (and a page, if it holds a request); one
    longer row and the group walks. Rows and groups that hold no request
    are not counted."""
    fits = (pages <= c) & ((pages > 0) == held[:, None])
    short = fits.reshape(-1, r, pages.shape[1]).all(axis=1)
    rows = held.reshape(-1, r).sum(axis=1)[:, None]       # a group's live
    return (int((short * rows).sum()), int(rows.sum()) * pages.shape[1],
            int((~short & (rows > 0)).sum()),
            int((rows > 0).sum()) * pages.shape[1])


class PagedLLMEngine(LLMEngine):
    """Continuous-batching engine over a paged KV pool.

    The serving loop (admission fusion, pipelined dispatch, demux, failure
    handling) is LLMEngine's; the device state and the prefill, decode,
    chunk, verify, prefix and restore programs are here. page budget:
    n_pages * page_size tokens TOTAL across slots — callers size it from
    the capacity plan (plan_capacity) instead of n_slots * max_seq.
    """

    def __init__(self, params, cfg: LlamaConfig, *, page_size: int = 128,
                 n_pages: Optional[int] = None, prefix_cache: bool = False,
                 kv_host_tier_bytes: int = 0, kv_redis=None,
                 kv_redis_ttl_s: Optional[float] = None,
                 conversation_pin_s: float = 600.0, **kw):
        # chunked prefill runs against bucket-sized per-job TEMPS and
        # scatters into pages once at the final chunk (_chunk_fn_paged);
        # speculative verify gathers pages into contiguous rows per layer
        # (llama_verify_step_paged). Both compose with the pool; spec+int8
        # KV and spec+chunk are refused by the loop's constructor
        self.page_size = page_size
        self._requested_pages = n_pages
        # what the model's family cannot serve yet is refused by name here,
        # before anything is built (models/protocol.py `refuses`)
        cfg.paged_model().refuse({
            "prefix_cache": prefix_cache,
            "kv_host_tier": kv_host_tier_bytes,
            "disagg": kw.get("disagg_role"),
            "speculative_tokens": kw.get("speculative_tokens"),
            "chunk_prefill_tokens": kw.get("chunk_prefill_tokens"),
            "kv_dtype": getattr(cfg, "kv_dtype", None),
            "int8_weights": isinstance(params, dict) and "lm_head_s" in params,
            "mesh": kw.get("mesh")})
        # prefix_cache=True shares whole prompt-prefix pages between
        # requests (refcounted, LRU-evicted back into the allocator) —
        # see tpu/prefixcache.py. int8 pools share scales alongside values
        # (the prefix program's gathered read dequantizes per page)
        self._prefix_enabled = bool(prefix_cache)
        # tiered KV (tpu/kvtier.py): prefix pages evicted from the pool
        # spill to a host-RAM LRU (optionally write-behind to Redis) and
        # restore by H2D copy on the next prefix hit instead of
        # re-prefilling. Built OUTSIDE _init_device_state on purpose: the
        # blobs are content-keyed host copies of deterministic KV, so they
        # stay valid across device resets (the pool and PrefixCache
        # rebuild; the tiers do not)
        self.kv_tier = None
        self.conversation_pin_s = float(conversation_pin_s)
        self._kv_spilled = 0    # lifetime page counts for /debug/engine
        self._kv_restored = 0
        if kv_host_tier_bytes:
            if not self._prefix_enabled:
                raise ValueError(
                    "kv_host_tier_bytes requires prefix_cache=True: tier "
                    "blobs are addressed by the prefix cache's chain keys")
            from .kvtier import HostKVTier, RedisKVTier

            cold = None
            if kv_redis is not None:
                cold = (kv_redis if isinstance(kv_redis, RedisKVTier)
                        else RedisKVTier(kv_redis, ttl_s=kv_redis_ttl_s))
            self.kv_tier = HostKVTier(kv_host_tier_bytes, page_size,
                                      cold=cold)
        # everything above can refuse: it runs before the loop half starts
        # its finisher thread
        super().__init__(params, cfg, **kw)
        self._init_device_state()

    # -- the model, through its protocol ---------------------------------------
    @property
    def model(self):
        """models/protocol.py `PagedModel` of this engine's config: what
        state the family holds beside the pools, its prefill and its decode
        step. Asked of the config each time, so an engine made without
        __init__ (the compile rehearsal) has it too."""
        return self.cfg.paged_model()

    # The pools, one a plane of the family's page (models/protocol.py
    # `planes`), in a list: the prefill and decode programs, the capacity
    # check and `/debug/engine` run over it. `k_cache` and `v_cache` name
    # the two pools of a family whose planes are K and V, for the programs
    # that only such a family runs (prefix tails, tier restores, hand-off
    # blobs, the int8 pools, verify, chunks: a family with other planes
    # refuses each by name) and for whoever reads the engine from outside.
    @property
    def k_cache(self):
        return self.pools[0]

    @k_cache.setter
    def k_cache(self, pool) -> None:
        self.pools[0] = pool

    @property
    def v_cache(self):
        return self.pools[1]

    @v_cache.setter
    def v_cache(self, pool) -> None:
        self.pools[1] = pool

    # -- device state ---------------------------------------------------------
    def _init_device_state(self) -> None:
        import jax

        jnp = self._jnp
        ps = self.page_size
        # default pool: every slot can reach max_seq_len; real deployments
        # pass the planned smaller n_pages
        n_pages = self._requested_pages or (
            self.n_slots * math.ceil(self.max_seq_len / ps) + 1)
        # an allocator a page group (models/protocol.py `groups`): the
        # PRIMARY group, the first that keeps every token, has the pool the
        # caller sized, and `allocator`, `_reservations` and a slot's
        # `pages` are its; a window group's pool holds every slot's whole
        # ring and its pages ride beside (`_more_reservations`, a slot's
        # `more_pages`, {group index: pages})
        model = self.model
        # a group's ring of pages a sequence (None: every token's pages)
        self._rings = rings = [group.ring(ps) for group in model.groups]
        if any(rings) and self.decode_block_size > ps:
            raise ValueError(
                f"decode_block_size {self.decode_block_size} is over "
                f"page_size {ps}: a window group's ring leaves one page "
                f"for a decode block to cross into")
        self._primary = next((i for i, ring in enumerate(rings)
                              if ring is None), 0)
        self.allocators = [
            PageAllocator(n_pages if i == self._primary
                          else self.n_slots * ring + 1, ps)
            for i, ring in enumerate(rings)]
        self.allocator = self.allocators[self._primary]
        self._reservations: Dict[int, List[int]] = {}
        self._more_reservations: Dict[int, Dict[int, List[int]]] = {}
        # pages reserved a group and the sequences that reserved them,
        # since the last reset (`/debug/engine` paging.groups)
        self._reserved_pages = [0] * len(rings)
        self._reserved_sequences = 0
        # prefix cache rebuilds with the pool: a device-state reset zeroes
        # the pages, so every cached entry is invalid by construction
        from .prefixcache import PrefixCache

        self.prefix = PrefixCache(ps) if self._prefix_enabled else None
        self._prefix_hits: Dict[int, List[int]] = {}
        # one pool a plane of the family's page a group, group by group;
        # a pool's leading axis counts its group's blocks
        L = model.kv_layers
        held_in = getattr(self.cfg, "kv_dtype", None) or self.cfg.dtype
        dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
              "float16": jnp.float16, "int8": jnp.int8}[held_in]
        # the capacity plan (budget_bytes) clamped n_slots and
        # max_seq_len; the pool derived from them must itself fit — check
        # explicitly, since an explicit n_pages bypasses the plan's sizing
        itemsize = {"bfloat16": 2, "float16": 2, "int8": 1}.get(held_in, 4)
        pool_bytes = sum(
            group.layers * model.plane_values * allocator.n_pages
            for group, allocator in zip(model.groups, self.allocators)
        ) * ps * itemsize
        if self._q8:  # f32 dequant scale pools ride along
            pool_bytes += 2 * L * n_pages * self.cfg.n_kv_heads * ps * 4
        if self.plan is not None:
            usable = int(self.plan.budget_bytes * 0.92)
            need = (self.plan.params_bytes + pool_bytes
                    + self.n_slots * self.cfg.state_bytes_per_slot
                    + self.plan.prefill_temp_bytes)
            if need > usable:
                raise ValueError(
                    f"page pool of {n_pages} pages ({pool_bytes >> 20} MiB) "
                    f"does not fit the budget: params + pool + prefill temps "
                    f"= {need >> 20} MiB > {usable >> 20} MiB usable")
        self.pools = [jnp.zeros(plane.pool_shape(group.layers,
                                                 allocator.n_pages, ps),
                                dtype=dt)
                      for group, allocator in zip(model.groups,
                                                  self.allocators)
                      for plane in model.planes]
        self.k_scale = self.v_scale = None
        if self._q8:
            self.k_scale = jnp.zeros((L, n_pages, self.cfg.n_kv_heads, ps),
                                     dtype=jnp.float32)
            self.v_scale = jnp.zeros_like(self.k_scale)
        B = self.n_slots
        # what a sequence holds beside its pages, a slot's worth each
        # (none for a model whose only cached state is pages): donated to
        # and returned by the step programs like the pools. A slot's state
        # is written whole by its prefill at admission
        self.state = tuple(jnp.zeros(shape, dtype=dtype)
                           for shape, dtype in self.model.state_shapes(B))
        # the program lookups since the last reset (`program_lookup`)
        self.lookups = LookupCount()
        # the family's decode counters, summed since the last reset
        self.model_counts = np.zeros(len(self.model.counters), np.int64)
        self.model_count_steps = 0
        # decode tokens placed in pages and the page writes that placed
        # them, since the last reset (`_note_page_writes`)
        self.write_tokens = self.write_pages = 0
        # the decode reads' folds (loop turns of the read's kernel), those
        # computed under a whole fold's width, the tokens they attended in
        # pages and the lanes they computed, since the last reset, and the
        # last block's fold width (`_note_page_reads`)
        self.read_folds = self.read_narrowed = 0
        self.read_tokens = self.read_lanes = 0
        self.read_pages_per_fold = None
        # and how its rows were walked (`_groups`): row reads in the short
        # rows' step, row reads, groups that walked, groups read
        self.read_groups = np.zeros(4, np.int64)
        # the same a group beside the primary: {group index: [folds,
        # tokens, lanes, pages a fold, narrowed folds, `_groups`' four]}
        self._more_reads = {i: [0, 0, 0, None, 0, np.zeros(4, np.int64)]
                            for i in range(len(rings)) if i != self._primary}
        self._tokens = jnp.zeros((B,), dtype=jnp.int32)
        self._positions = jnp.zeros((B,), dtype=jnp.int32)
        self._temps = self._temps_init(B)
        self.rng = jax.random.PRNGKey(next(self._reset_counter))
        if self.mesh is not None:
            self._place_state()

    def _place_state(self) -> None:
        """Commit device state to the mesh: the STACKED [L, P, Hkv, dh, ps]
        pools shard their KV-head axis over tp, loop state replicates.
        Committed shardings propagate into every compiled program; XLA
        inserts the tp collectives."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.sharding import kv_cache_spec

        cache_s = NamedSharding(self.mesh, kv_cache_spec())
        rep = NamedSharding(self.mesh, PartitionSpec())
        self.pools = [jax.device_put(pool, cache_s) for pool in self.pools]
        if self._q8:
            from ..parallel.sharding import kv_scale_pool_spec

            scale_s = NamedSharding(self.mesh, kv_scale_pool_spec())
            self.k_scale = jax.device_put(self.k_scale, scale_s)
            self.v_scale = jax.device_put(self.v_scale, scale_s)
        self._tokens = jax.device_put(self._tokens, rep)
        self._positions = jax.device_put(self._positions, rep)
        self._temps = jax.device_put(self._temps, rep)
        self.rng = jax.device_put(self.rng, rep)

    def state_bytes(self) -> int:
        """The per-slot state beside the pools, all slots."""
        return sum(a.size * a.dtype.itemsize for a in self.state)

    def pool_bytes(self) -> int:
        total = sum(pool.size * pool.dtype.itemsize for pool in self.pools)
        if self.k_scale is not None:  # int8: f32 scale pools are pool bytes too
            total += 2 * self.k_scale.size * self.k_scale.dtype.itemsize
        return total

    # -- admission: page reservation ------------------------------------------
    def submit(self, prompt_tokens, max_new_tokens: int = 128,
               temperature: float = 0.0, stop_tokens=None,
               span=None, priority: int = 0,
               min_tokens: int = 0, top_p: float = 0.0,
               top_k: int = 0, traceparent=None,
               qos_class=None, tenant: str = "") -> GenerationRequest:
        """Reject requests whose reservation could NEVER fit the pool:
        parking them would permanently occupy the admission heap's head
        for their priority class behind an allocation that cannot
        succeed."""
        total = min(len(prompt_tokens) + max_new_tokens, self.max_seq_len)
        need = self.allocator.pages_for(total)
        usable = self.allocator.n_pages - 1
        if need > usable:
            raise ValueError(
                f"request needs {need} pages ({total} tokens at page_size="
                f"{self.allocator.page_size}) but the pool has only {usable} "
                f"usable pages; shrink max_new_tokens or grow n_pages")
        return super().submit(prompt_tokens, max_new_tokens, temperature,
                              stop_tokens, span=span, priority=priority,
                              min_tokens=min_tokens, top_p=top_p,
                              top_k=top_k, traceparent=traceparent,
                              qos_class=qos_class, tenant=tenant)

    def submit_handoff(self, prompt_tokens, emitted, **kw):
        """submit()'s never-fits rejection, applied to the hand-off path:
        a hand-off whose reservation could never fit this pool must be
        refused at the edge (the coordinator then falls back), not parked
        forever at the head of its priority class."""
        total = min(len(prompt_tokens) + kw.get("max_new_tokens", 128),
                    self.max_seq_len)
        need = self.allocator.pages_for(total)
        usable = self.allocator.n_pages - 1
        if need > usable:
            raise ValueError(
                f"hand-off needs {need} pages ({total} tokens at page_size="
                f"{self.allocator.page_size}) but the pool has only {usable} "
                f"usable pages; shrink max_new_tokens or grow n_pages")
        return super().submit_handoff(prompt_tokens, emitted, **kw)

    def _request_pages(self, request: GenerationRequest) -> int:
        # resume_tokens + remaining budget == prompt + max_new for fresh
        # requests AND for replays (delivered tokens moved from budget to
        # window), so reservations are reset-stable by construction
        total = min(len(request.resume_tokens)
                    + (request.max_new_tokens - request.generated),
                    self.max_seq_len)
        return self.allocator.pages_for(total)

    def _admission_ready(self, request: GenerationRequest) -> bool:
        if request.id in self._reservations:
            return True
        with self.steps.seg("page_alloc"):
            return self._reserve_pages(request)

    def _reserve_pages(self, request: GenerationRequest) -> bool:
        """Page reservation + prefix match/eviction — the `page_alloc`
        step segment (a pool under pressure shows up here, including the
        page-wait retries an exhausted pool causes)."""
        shared: List[int] = []
        # hand-off arrivals skip the prefix walk: their KV arrives as page
        # blobs (landed by _admit_handoff into the plain reservation), so a
        # prefix match would double-provide the same positions — and on a
        # fallback the blobs are dropped BEFORE re-parking, so the recompute
        # pass gets the full prefix/tier treatment like any replay
        if self.prefix is not None and request.handoff_blobs is None:
            if request.id not in self._prefix_hits:
                hit = self.prefix.match(request.resume_tokens)
                if self.kv_tier is not None:
                    # extend the HBM hit from the host/Redis tiers: a
                    # restored page costs one H2D page copy instead of a
                    # page of prefill compute. Nested inside page_alloc —
                    # seg() subtracts child time from the parent, so the
                    # restore cost is attributable on its own
                    with self.steps.seg("kv_restore"):
                        hit = self._restore_from_tier(request, hit)
                if hit and self._tail_routes_to_chunk(request, hit):
                    # the tail would still chunk: drop the hit NOW, before
                    # the reservation is sized — deciding later would leave
                    # the reservation short by the matched pages and scatter
                    # prompt KV into the garbage page (r4 review). The
                    # finished chunk job still inserts, so the NEXT
                    # identical prefix admits tail-only
                    for page_id in hit:
                        self.prefix.unref(page_id)
                    hit = []
                self._prefix_hits[request.id] = hit
            shared = self._prefix_hits[request.id]
        need = self._request_pages(request) - len(shared)
        pages = self.allocator.alloc(need)
        if pages is None and self.prefix is not None:
            # idle cache pages are reclaimable capacity: evict LRU entries
            # into the free list and retry before parking the request
            # (eviction spills the pages' KV to the host tier first when
            # tiering is on — see _evict_prefix_pages)
            self.allocator.release(
                self._evict_prefix_pages(need - self.allocator.free_pages))
            pages = self.allocator.alloc(need)
        if pages is None:
            self._obs.counter("app_tpu_page_waits_total")
            if self.recorder is not None:
                # once per request: _admission_ready retries at loop speed
                # while the pool is exhausted, and one timeline entry is
                # the evidence an operator needs
                self.recorder.record_event(request.id, "page_wait",
                                           once=True, need=need)
            return False
        self._reservations[request.id] = pages
        if not self._reserve_more(request):
            self.allocator.release(self._reservations.pop(request.id))
            return False
        self._note_reserved(request)
        return True

    def _group_pages(self, request: GenerationRequest, index: int) -> int:
        """The pages `request` holds in group `index`: every token's, or at
        most the ring of a window group."""
        need, ring = self._request_pages(request), self._rings[index]
        return need if ring is None else min(need, ring)

    def _reserve_more(self, request: GenerationRequest) -> bool:
        """The request's pages in the groups beside the primary. Their
        pools hold every slot's whole ring, so this fails only for a
        caller that reserves more requests than there are slots."""
        if len(self.allocators) == 1 or request.id in self._more_reservations:
            return True
        more = {}
        for index, allocator in enumerate(self.allocators):
            if index == self._primary:
                continue
            pages = allocator.alloc(self._group_pages(request, index))
            if pages is None:
                for had, got in more.items():
                    self.allocators[had].release(got)
                return False
            more[index] = pages
        self._more_reservations[request.id] = more
        return True

    def _note_reserved(self, request: GenerationRequest) -> None:
        self._reserved_sequences += 1
        self._reserved_pages[self._primary] += len(
            self._reservations[request.id])
        for index, pages in self._more_reservations.get(
                request.id, {}).items():
            self._reserved_pages[index] += len(pages)

    def _release_more(self, more) -> None:
        for index, pages in (more or {}).items():
            self.allocators[index].release(pages)

    def _abort_admission(self, request: GenerationRequest) -> None:
        pages = self._reservations.pop(request.id, None)
        if pages is not None:
            self.allocator.release(pages)
        self._release_more(self._more_reservations.pop(request.id, None))
        shared = self._prefix_hits.pop(request.id, None)
        if shared:
            for page_id in shared:
                self.prefix.unref(page_id)

    def _tail_bucket(self, request: GenerationRequest,
                     shared: List[int]) -> int:
        from .executor import next_bucket

        tail = len(request.resume_tokens) - len(shared) * self.page_size
        return next_bucket(max(1, tail), self.prefill_buckets)

    def _tail_routes_to_chunk(self, request: GenerationRequest,
                              shared: List[int]) -> bool:
        return bool(self.chunk_prefill_tokens
                    and self._tail_bucket(request, shared)
                    > self.chunk_prefill_tokens)

    def _admission_bucket(self, request: GenerationRequest) -> int:
        """On a prefix hit, the admission window is the un-cached TAIL
        (chunk-routed hits were already dropped in _admission_ready,
        before the reservation was sized)."""
        if self.prefix is None:
            return super()._admission_bucket(request)
        shared = self._prefix_hits.get(request.id) or []
        if not shared:
            return super()._admission_bucket(request)
        return self._tail_bucket(request, shared)

    def _release_slot_pages(self, slot) -> None:
        """Return a slot's pages to the allocator (prefix-owned pages stay
        cache-resident via unref) — shared by the normal finish path and
        the disaggregated hand-off evacuation."""
        if slot.pages is None:
            return
        self._release_more(slot.more_pages)
        slot.more_pages = None
        if self.prefix is not None:
            keep = []
            for page_id in slot.pages:
                if self.prefix.owns(page_id):
                    self.prefix.unref(page_id)   # stays cache-resident
                else:
                    keep.append(page_id)
            self.allocator.release(keep)
        else:
            self.allocator.release(slot.pages)
        slot.pages = None

    def _release_slot_for_preempt(self, slot) -> None:
        """QoS preemption on a paged engine: unlike a device reset (which
        rebuilds the whole allocator), the pool survives — so this slot's
        pages must be returned explicitly before the evacuation, exactly
        like the finish path (prefix-owned pages stay cache-resident, so
        the preempted request's re-prefill will mostly be a prefix hit)."""
        self._release_slot_pages(slot)
        super()._release_slot_for_preempt(slot)

    def tier_inventory(self, limit: int = 64):
        """Bounded {key, tokens} listing of the host tier's newest pages —
        served at /debug/kvtier for peers' warm-boot pre-warm."""
        if self.kv_tier is None:
            return []
        return self.kv_tier.inventory(limit)

    def prewarm_from_tier(self, entries, limit: int = 64) -> int:
        """Warm-boot pre-warm: pull peer-advertised pages into host RAM
        through the tier's own get() (shared cold tier hits promote, and
        every page is content-verified against its token window). Runs
        off the serving path at boot; returns pages now resident."""
        if self.kv_tier is None:
            return 0
        warmed = 0
        for row in list(entries)[:max(0, int(limit))]:
            try:
                key = int(row["key"])
                tokens = [int(t) for t in row["tokens"]]
            except (KeyError, TypeError, ValueError):
                continue
            if self.kv_tier.get(key, tokens) is not None:
                warmed += 1
        if warmed:
            self._obs.counter("app_tpu_elastic_prewarm_pages_total", warmed)
        return warmed

    def _export_slot_kv(self, slot, request):
        """Migration export for a LIVE decode slot: the _handoff_slot D2H
        recipe generalized past the prefill boundary — the pages cover
        slot.length positions (prompt + all-but-the-last emitted token),
        so the peer's _admit_handoff content-verify window matches
        exactly. Any mismatch (mid-flight oddity, no pages) degrades to
        the blob-less export — peer-side recompute, never a wrong blob."""
        n_ctx = slot.length
        if (slot.pages is None or n_ctx <= 0 or self.state
                or n_ctx != len(request.resume_tokens) - 1):
            return None, max(0, len(request.resume_tokens) - 1)
        from .kvtier import PageBlob

        ps = self.page_size
        window = request.resume_tokens[:n_ctx]
        n_kv = self.allocator.pages_for(n_ctx)
        try:
            ids = np.asarray(slot.pages[:n_kv], dtype=np.int32)
            pulls = [self.k_cache[:, ids], self.v_cache[:, ids]]
            if self._q8:
                pulls += [self.k_scale[:, ids], self.v_scale[:, ids]]
            host = self._fetch_host(*pulls)
        except Exception as exc:  # noqa: BLE001 - a failed pull degrades to replay
            if self.logger is not None:
                self.logger.errorf("migration KV pull failed for %s: %s",
                                   request.id, exc)
            return None, n_ctx
        k, v = host[0], host[1]
        ks, vs = (host[2], host[3]) if self._q8 else (None, None)
        blobs = []
        for i in range(n_kv):
            blobs.append(PageBlob(
                tuple(window[i * ps:(i + 1) * ps]),
                k[:, i], v[:, i],
                None if ks is None else ks[:, i],
                None if vs is None else vs[:, i]))
        return blobs, n_ctx

    def _finish_slot(self, slot) -> None:
        self._release_slot_pages(slot)
        super()._finish_slot(slot)
        # pool gauges ride the off-loop finisher: values are READ here on
        # the loop thread (allocator state is loop-owned), flushed off it
        used, free = self.allocator.used_pages, self.allocator.free_pages
        by_group = [(group.name, allocator.used_pages) for group, allocator
                    in zip(self.model.groups, self.allocators)]

        routing = (self.model_snapshot().get("routing", {})
                   if self.model_count_steps else {})

        def flush() -> None:
            self._obs.gauge("app_tpu_pages_used", used)
            self._obs.gauge("app_tpu_kv_pool_pages", used, kind="used")
            self._obs.gauge("app_tpu_kv_pool_pages", free, kind="free")
            for name, pages in by_group:
                self._obs.gauge("app_tpu_pool_pages", pages, group=name)
            for what, value in routing.items():
                self._obs.gauge("app_tpu_moe_routing", value, what=what)

        self._run_off_loop(flush)

    # -- tiered KV: spill on evict, restore on hit ----------------------------
    @loop_only
    def _evict_prefix_pages(self, n: int) -> List[int]:
        """prefix.evict + KV spill: fetch the evicted pages' KV to the
        host (the async-D2H machinery) and hand the blobs to the tier
        BEFORE the page ids return to the allocator — once reallocated,
        the pool slots are overwritten and the content is gone."""
        entries = self.prefix.evict_entries(n)
        if entries and self.kv_tier is not None:
            try:
                self._spill_pages(entries)
            except Exception:  # noqa: BLE001 - spill is an optimization:
                pass           # losing it degrades to recompute, never worse

        return [page_id for _, page_id, _ in entries]

    def _spill_pages(self, entries) -> None:
        from .kvtier import PageBlob

        ids = np.asarray([pid for _, pid, _ in entries], dtype=np.int32)
        # batched gather: one [L, n, Hkv, dh, ps] slice per pool — a NEW
        # buffer, so later donation of the pool cannot invalidate it; all
        # D2H copies start async before the first blocks
        pulls = [self.k_cache[:, ids], self.v_cache[:, ids]]
        if self._q8:
            pulls += [self.k_scale[:, ids], self.v_scale[:, ids]]
        host = self._fetch_host(*pulls)
        k, v = host[0], host[1]
        ks, vs = (host[2], host[3]) if self._q8 else (None, None)
        stored = 0
        for i, (key, _, toks) in enumerate(entries):
            blob = PageBlob(toks, k[:, i], v[:, i],
                            None if ks is None else ks[:, i],
                            None if vs is None else vs[:, i])
            if self.kv_tier.put(key, blob):
                stored += 1
        if stored:
            self._kv_spilled += stored
            self._obs.counter("app_tpu_kv_tier_spilled_total", stored)

    def _restore_from_tier(self, request: GenerationRequest,
                           hit: List[int]) -> List[int]:
        """Continue the prefix walk past the HBM hit through the host (and
        Redis) tiers: consecutive content-verified tier hits allocate
        fresh pages and restore by H2D scatter, so only the genuinely
        un-cached tail re-prefills. Returns the extended hit list with the
        restored pages ref'd exactly like matched ones (insert grants the
        owner ref; _finish_slot/_abort_admission release it)."""
        tokens = request.resume_tokens
        ps = self.page_size
        matchable = max(0, (len(tokens) - 1) // ps)
        start = len(hit)
        if start >= matchable:
            return hit
        tier = self.kv_tier
        L, _, Hkv, dh, _ = self.k_cache.shape
        pool_dt = np.dtype(self.k_cache.dtype)
        corrupt0 = tier.corrupt + (tier.cold.corrupt if tier.cold else 0)
        keys = self.prefix.keys_for(tokens, matchable)
        blobs = []
        for i in range(start, matchable):
            blob = tier.get(keys[i], tokens[i * ps:(i + 1) * ps])
            if blob is None:
                break
            # config-skew guard (a Redis blob can outlive the process that
            # wrote it): a blob whose shape/dtype does not match THIS pool
            # is a miss, not a crash
            if (blob.k.shape != (L, Hkv, dh, ps)
                    or blob.k.dtype != pool_dt
                    or (self._q8 and blob.k_scale is None)):
                break
            blobs.append(blob)
        corrupt = (tier.corrupt
                   + (tier.cold.corrupt if tier.cold else 0)) - corrupt0
        if corrupt:
            self._obs.counter("app_tpu_kv_tier_corrupt_total", corrupt)
        if blobs:
            self._obs.counter("app_tpu_kv_tier_hits_total", len(blobs))
        missed = matchable - start - len(blobs)
        if missed:
            self._obs.counter("app_tpu_kv_tier_misses_total", missed)
        if not blobs:
            return hit
        need = len(blobs)
        pages = self.allocator.alloc(need)
        if pages is None:
            self.allocator.release(
                self._evict_prefix_pages(need - self.allocator.free_pages))
            pages = self.allocator.alloc(need)
        if pages is None:
            # pool too tight to host the restored pages: recompute the
            # tail instead of deadlocking admission on its own cache
            return hit
        try:
            self._h2d_restore(pages, blobs)
        except Exception:  # noqa: BLE001 - restore is optional: fall back
            self.allocator.release(pages)   # to recompute; a real device
            return hit                      # loss resurfaces at dispatch
        # register the restored pages under their chain keys: insert sees
        # the first `start` keys already cached (skipped) and grants the
        # owner ref on the new ones — the SAME release discipline as
        # freshly-prefilled pages, so finish/abort need no special case
        self.prefix.insert(list(tokens[:(start + need) * ps + 1]),
                           list(hit) + pages)
        self._kv_restored += need
        self._obs.counter("app_tpu_kv_tier_restored_total", need)
        if self.recorder is not None:
            self.recorder.record_event(request.id, "kv_restore",
                                       pages=need)
        return list(hit) + pages

    def _restore_fn(self):
        def restore(k_pool, v_pool, pages, new_k, new_v):
            """Scatter n restored pages into the pool. Rows padding n up
            to the compiled pow2 width carry page id 0 — the garbage page
            — with zero payloads, so padding (and its duplicate indices)
            can never touch a live page."""
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            k_pool = k_pool.at[:, pages].set(new_k)
            v_pool = v_pool.at[:, pages].set(new_v)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return k_pool, v_pool

        return restore

    def _restore_fn_q8(self):
        def restore(k_pool, v_pool, k_scale, v_scale, pages, new_k, new_v,
                    new_ks, new_vs):
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            k_pool = k_pool.at[:, pages].set(new_k)
            v_pool = v_pool.at[:, pages].set(new_v)
            k_scale = k_scale.at[:, pages].set(new_ks)
            v_scale = v_scale.at[:, pages].set(new_vs)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return k_pool, v_pool, k_scale, v_scale

        return restore

    @program_lookup
    def _restore_program(self, n: int):
        L, _, Hkv, dh, ps = self.k_cache.shape
        kv = (_shaped((L, n, Hkv, dh, ps), self.k_cache.dtype),) * 2
        ids = _shaped((n,))
        if self._q8:
            scales = (_shaped((L, n, Hkv, ps), np.float32),) * 2
            args = (self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                    ids, *kv, *scales)
            return self.executor.compile(
                f"llama-paged-restore-q8-N{n}{self._id_tag}",
                self._restore_fn_q8(), args, donate_argnums=(0, 1, 2, 3))
        args = (self.k_cache, self.v_cache, ids, *kv)
        return self.executor.compile(
            f"llama-paged-restore-N{n}{self._id_tag}",
            self._restore_fn(), args, donate_argnums=(0, 1))

    def _h2d_restore(self, pages: List[int], blobs) -> None:
        jnp = self._jnp
        L, _, Hkv, dh, ps = self.k_cache.shape
        n = _pow2_at_least(len(pages))
        ids = np.zeros((n,), dtype=np.int32)   # pads -> garbage page 0
        ids[:len(pages)] = pages
        new_k = np.zeros((L, n, Hkv, dh, ps),
                         dtype=np.dtype(self.k_cache.dtype))
        new_v = np.zeros_like(new_k)
        for i, blob in enumerate(blobs):
            new_k[:, i] = blob.k
            new_v[:, i] = blob.v
        program = self._restore_program(n)
        if self._q8:
            new_ks = np.zeros((L, n, Hkv, ps), dtype=np.float32)
            new_vs = np.zeros_like(new_ks)
            for i, blob in enumerate(blobs):
                new_ks[:, i] = blob.k_scale
                new_vs[:, i] = blob.v_scale
            (self.k_cache, self.v_cache, self.k_scale,
             self.v_scale) = program(
                self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                jnp.asarray(ids), jnp.asarray(new_k), jnp.asarray(new_v),
                jnp.asarray(new_ks), jnp.asarray(new_vs))
        else:
            self.k_cache, self.v_cache = program(
                self.k_cache, self.v_cache, jnp.asarray(ids),
                jnp.asarray(new_k), jnp.asarray(new_v))

    def pin_conversation(self, conversation_id: str,
                         tokens: Sequence[int]) -> int:
        """Pin a conversation trunk's chain keys through the HOST tier for
        conversation_pin_s seconds (callable from handler threads: key
        derivation is pure, the tier locks internally). Pins protect
        host-tier residency ONLY — HBM eviction stays unconditional,
        because a pool that cannot evict cannot admit (an HBM pin could
        deadlock admission); the spill path preserves the pinned trunk on
        its way down anyway. conversation_id is observability context."""
        if self.kv_tier is None or self.prefix is None:
            return 0
        n_full = len(tokens) // self.page_size
        if n_full <= 0:
            return 0
        keys = self.prefix.keys_for(tokens, n_full)
        pinned = self.kv_tier.pin(keys, self.conversation_pin_s)
        if pinned:
            self._obs.counter("app_tpu_kv_tier_pinned_total", pinned)
        return pinned

    # -- programs -------------------------------------------------------------
    def warmup(self, k_variants: bool = False) -> None:
        """Pre-compile single-admission prefill buckets and the decode
        programs of every table width an admission can produce.

        k_variants=True additionally compiles every fused-admission width
        per bucket (_admission_widths) and every table width up to
        max_seq_len. Organic (staggered) arrivals admit in unpredictable
        group sizes, so without this a production server pays a first-use
        compile mid-request whenever traffic first produces a new
        (bucket, K) — a TTFT spike. Costs buckets x log4(slots) compiles
        at boot, amortized by the compile cache.

        Safe against an already-started loop: compiles run under the same
        state lock the loop's dispatch phase takes."""
        with self._state_lock:
            ks = (sorted(_admission_widths(self.n_slots)) if k_variants
                  else [1])
            chunk = self.chunk_prefill_tokens
            for bucket in self.prefill_buckets:
                # buckets routed to the chunk path skip the (dead) fused
                # program
                if not (chunk and bucket > chunk):
                    for K in ks:
                        self._prefill_program(bucket, K)
            if chunk:
                for bucket in self.prefill_buckets:
                    if bucket > chunk:  # warm that bucket's mid+final pair
                        self._chunk_program_paged(chunk, 1, bucket,
                                                  final=False)
                        self._chunk_program_paged(chunk, 1, bucket,
                                                  final=True)
            if self.prefix is not None and self.prefill_buckets:
                # the feature's headline case is the SECOND request with a
                # shared system prompt: its tail admits at the smallest
                # bucket against a table spanning the full prompt's pages.
                # Warm that variant per bucket-width so the first hit
                # doesn't stall the loop on a compile (r4 review)
                tail_b = min(self.prefill_buckets)
                for bucket in self.prefill_buckets:
                    self._prefix_program(
                        tail_b, 1,
                        _pow2_at_least(self.allocator.pages_for(bucket)))
            if self.kv_tier is not None or self.disagg_role == "decode":
                # restore widths are organic (however many consecutive
                # tier hits the walk finds — or however many hand-off
                # pages a wave lands — pow2-padded); warm the small ones
                # so a conversation's first resume (or the decode pool's
                # first hand-off) doesn't compile on the loop thread
                for n in (1, 2):
                    self._restore_program(n)
            # the table widths dispatch can ask for: _build_table uses
            # pow2(widest_pages + 1) over the active slots' reservations.
            # Warm every width an ADMISSION can produce — a prompt within
            # the largest bucket plus a page of generation, and anything
            # shorter (a lone short request has a narrower table than the
            # widest one) — or the first such request compiles on the loop
            # thread. k_variants warms the rest too, up to max_seq_len: a
            # long generation then never meets a cold width either
            reach = (self.max_seq_len if k_variants else min(
                max(self.prefill_buckets or (self.page_size,))
                + self.page_size, self.max_seq_len))
            warm_widths = {
                _pow2_at_least(pages + 1)
                for pages in range(1, self.allocator.pages_for(reach) + 1)}
            for width in sorted(warm_widths):
                self._decode_program_paged(width)
                if self.decode_block_size > 1:
                    # the adaptive short-block variant fires under queue
                    # pressure — exactly when a compile stall hurts most
                    self._decode_program_paged(
                        width, max(1, self.decode_block_size // 2))
                if self.speculative_tokens:
                    self._verify_program(width)

    def _prefill_fn(self, bucket: int, K: int):
        model, mesh, jnp = self.model, self.mesh, self._jnp
        top_k, planes, g = self.top_k, len(model.planes), len(model.groups)
        n = planes * g
        from .sampling import sample_tokens

        def prefill(params, *rest):
            """(params, a pool a plane a group, ptokens, a ptable a group,
            slots, lengths, tokens, positions, temps, new_temps, rng,
            *state). Fused K-way
            paged admission: the model's prefill of the [K, bucket]
            window, what it keeps a token scattered into the slots' pages,
            plane by plane, and its rows' final states into the slots'
            state (`state`: the family's per-slot arrays, none for a model
            that holds only pages), first tokens sampled, loop state
            spliced. ptable: [K, ceil(bucket/ps)] page ids, column j the
            page of the prompt's tokens [j ps, (j + 1) ps); a window
            group's names its ring's pages at the prompt's last ring of
            columns and the garbage page before them (`_prefill_tables`)."""
            ptokens, *ptables = rest[n:n + 1 + g]
            (slots, lengths, tokens, positions, temps,
             new_temps, rng, *state) = rest[n + 1 + g:]
            pools = [_pin_standard_layout(pool) for pool in rest[:n]]
            last, windows, rows = model.prefill(params, ptokens, lengths,
                                                mesh)
            # scatter the window into pages: token t of row k goes to
            # (ptable[k, t // ps], t % ps); pad junk past lengths[k] is
            # redirected to the garbage page so live pages stay clean
            # (a plane with a stride: the columns that exist, whole pages
            # of them, width-minor)
            starts = jnp.zeros_like(lengths)
            pools = [paged_write_window(pool, window, ptables[i // planes],
                                        starts, lengths)
                     if model.planes[i % planes].stride == 1
                     else paged_write_columns(
                         pool, window, ptables[i // planes],
                         model.planes[i % planes].columns(lengths))
                     for i, (pool, window) in enumerate(zip(pools, windows))]
            # a slot's state is written whole, in place (donated)
            state = tuple(held.at[:, slots].set(row.astype(held.dtype))
                          for held, row in zip(state, rows))
            first, rng = sample_tokens(last, rng, new_temps, top_k=top_k)
            tokens = tokens.at[slots].set(first)
            positions = positions.at[slots].set(lengths)
            temps = temps.at[slots].set(new_temps)
            pools = [_pin_standard_layout(pool) for pool in pools]
            return (*pools, tokens, positions, temps, rng, first, *state)

        return prefill

    def _prefill_fn_q8(self, bucket: int, K: int):
        """MIRRORS the paged _prefill_fn with int8 pools + scale pools:
        full-precision window forward into bf16 temps, quantize per
        token/head, scatter values and scales into the pages."""
        cfg, mesh = self.cfg, self.mesh
        jnp = self._jnp
        top_k = self.top_k
        from ..models.blocks import np_dtype
        from .sampling import sample_tokens

        def prefill(params, k_pool, v_pool, k_scale, v_scale, ptokens,
                    ptable, slots, lengths, tokens, positions, temps,
                    new_temps, rng):
            L, P, Hkv, dh, _ = k_pool.shape
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            tmp_k = jnp.zeros((L, K, Hkv, dh, bucket),
                              dtype=np_dtype(cfg.dtype))
            tmp_v = jnp.zeros_like(tmp_k)
            pos_grid = jnp.broadcast_to(
                jnp.arange(bucket, dtype=jnp.int32)[None, :], (K, bucket))
            last, tmp_k, tmp_v = llama_prefill_last(
                params, cfg, ptokens, pos_grid, lengths, tmp_k, tmp_v, mesh)
            k8, ks = quantize_kv(tmp_k, axis=-2)   # scales [L, K, Hkv, bucket]
            v8, vs = quantize_kv(tmp_v, axis=-2)
            k_pool, v_pool = paged_write_prefill_stacked(
                k_pool, v_pool, k8, v8, ptable, lengths)
            k_scale = paged_write_prefill_scales(k_scale, ks, ptable, lengths)
            v_scale = paged_write_prefill_scales(v_scale, vs, ptable, lengths)
            first, rng = sample_tokens(last, rng, new_temps, top_k=top_k)
            tokens = tokens.at[slots].set(first)
            positions = positions.at[slots].set(lengths)
            temps = temps.at[slots].set(new_temps)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return (k_pool, v_pool, k_scale, v_scale, tokens, positions,
                    temps, rng, first)

        return prefill

    @program_lookup
    def _prefill_program(self, bucket: int, K: int):
        n_ptable = max(1, math.ceil(bucket / self.page_size))
        new_temps = _shaped(self._temps_shape(K), np.float32)
        if self._q8:
            args = (self.params, self.k_cache, self.v_cache, self.k_scale,
                    self.v_scale,
                    _shaped((K, bucket)), _shaped((K, n_ptable)),
                    _shaped((K,)), _shaped((K,)),
                    self._tokens, self._positions, self._temps,
                    new_temps, self.rng)
            return self.executor.compile(
                f"llama-paged-prefill-q8-{bucket}x{K}{self._id_tag}",
                self._prefill_fn_q8(bucket, K),
                args, donate_argnums=(1, 2, 3, 4, 9, 10, 11))
        args = (self.params, *self.pools,
                _shaped((K, bucket)),
                *[_shaped((K, n_ptable)) for _ in self.allocators],
                _shaped((K,)), _shaped((K,)),
                self._tokens, self._positions, self._temps,
                new_temps, self.rng, *self.state)
        # the pools, the loop's vectors (past the tables, one a group),
        # the state
        n = len(self.pools)
        at = n + len(self.allocators) - 1
        return self.executor.compile(
            f"{self.model.program_tag}-paged-prefill-{bucket}x{K}{self._id_tag}",
            self._prefill_fn(bucket, K), args,
            donate_argnums=tuple(range(1, 1 + n)) + (at + 5, at + 6, at + 7)
            + tuple(range(at + 10, at + 10 + len(self.state))))

    def _decode_fn_paged(self, block: int, n_table: int):
        model, mesh = self.model, self.mesh
        top_k, planes, g = self.top_k, len(model.planes), len(model.groups)
        n = planes * g
        import jax
        import jax.numpy as jnp

        from .sampling import sample_tokens

        def decode(params, *rest):
            """(params, a pool a plane a group, a table a group, tokens,
            positions, temps, rng, *state). `block` paged decode steps
            under scan; the primary group's table [B, n_table], a window
            group's [B, ring] (`_build_tables`); a family of one group is
            handed its one table, a family of several all of them
            (models/protocol.py); `state` the family's per-slot arrays (none for a
            model that holds only pages), carried and returned like the
            pools. What the block's new tokens must keep waits in its
            tail, one a plane (ops/paged_attention `plane_tail`: made
            here, carried by the scan, dead at return) and the pools are
            only read until the scan is over; then ONE flush writes each
            live row's page once, every plane. A row that holds no
            request (its table starts at the garbage page) flushes
            nothing. A family that counts (models/protocol.py `counters`)
            gives a row of int32 a step; their sum over the block rides
            below the block's tokens, so one copy to the host carries
            both."""
            tables = rest[n:n + g]
            tokens, positions, temps, rng, *state = rest[n + g:]
            table = tables[0] if g == 1 else tuple(tables)
            pools = tuple(_pin_standard_layout(pool) for pool in rest[:n])

            def step(carry, t):
                tail, held, tok, pos, rng = carry
                logits, tail, held, counted = model.decode(
                    params, tok, pos, pools, table, held, tail, t, mesh)
                nxt, rng = sample_tokens(logits, rng, temps, top_k=top_k)
                return (tail, held, nxt, pos + 1, rng), (nxt, counted)

            strided = [plane.stride > 1 for plane in model.planes] * g
            tail = tuple(
                column_tail(pool, tokens.shape[0],
                            -(-block // model.planes[i % planes].stride))
                if strided[i]
                else plane_tail(pool, tokens.shape[0], block, mesh)
                for i, pool in enumerate(pools))
            (tail, state, tok, pos, rng), (out, counted) = jax.lax.scan(
                step, (tail, tuple(state), tokens, positions, rng),
                jnp.arange(block, dtype=jnp.int32))
            # a flush a group, through its own table (a window group's is
            # its ring)
            flushed = []
            rings = [group.ring(pools[0].shape[-1]) for group in model.groups]
            # (the planes of a value a token in one call; a plane with a
            # stride the columns its block completed, a scatter of its own)
            for i, (table, ring) in enumerate(zip(tables, rings)):
                mine = range(i * planes, (i + 1) * planes)
                live = holds_request(table)
                dense = [j for j in mine if not strided[j]]
                written = dict(zip(dense, flush_planes(
                    [pools[j] for j in dense], [tail[j] for j in dense],
                    table, positions, jnp.where(live, block, 0), mesh=mesh,
                    ring=ring)))
                for j in set(mine) - set(dense):
                    plane = model.planes[j % planes]
                    start = plane.columns(positions)
                    written[j] = flush_columns(
                        pools[j], tail[j], table, start, jnp.where(
                            live, plane.columns(positions + block) - start,
                            0))
                flushed += [written[j] for j in mine]
            pools = [_pin_standard_layout(pool) for pool in flushed]
            out = out.T
            if counted is not None:
                below = jnp.zeros((counted.shape[1], block), out.dtype)
                out = jnp.concatenate(
                    [out, below.at[:, 0].set(counted.sum(axis=0))])
            return (*pools, tok, pos, rng, out, *state)

        return decode

    def _decode_fn_paged_q8(self, block: int, n_table: int):
        """MIRRORS _decode_fn_paged over int8 pools + scale pools."""
        cfg, mesh = self.cfg, self.mesh
        top_k = self.top_k
        import jax

        from ..models.llama import llama_decode_step_paged_q8
        from .sampling import sample_tokens

        def decode(params, k_pool, v_pool, k_scale, v_scale, table, tokens,
                   positions, temps, rng):
            def step(carry, _):
                kp, vp, ks, vs, tok, pos, rng = carry
                logits, kp, vp, ks, vs = llama_decode_step_paged_q8(
                    params, cfg, tok, pos, kp, vp, ks, vs, table, mesh)
                nxt, rng = sample_tokens(logits, rng, temps, top_k=top_k)
                return (kp, vp, ks, vs, nxt, pos + 1, rng), nxt

            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            (k_pool, v_pool, k_scale, v_scale, tok, pos, rng), out = \
                jax.lax.scan(step, (k_pool, v_pool, k_scale, v_scale,
                                    tokens, positions, rng), None,
                             length=block)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return k_pool, v_pool, k_scale, v_scale, tok, pos, rng, out.T

        return decode

    @program_lookup
    def _decode_program_paged(self, n_table: int, block: Optional[int] = None):
        block = block or self.decode_block_size
        if self._q8:
            args = (self.params, self.k_cache, self.v_cache, self.k_scale,
                    self.v_scale, _shaped((self.n_slots, n_table)),
                    self._tokens, self._positions, self._temps, self.rng)
            return self.executor.compile(
                f"llama-paged-decode-q8-x{block}-NP{n_table}{self._id_tag}",
                self._decode_fn_paged_q8(block, n_table), args,
                donate_argnums=(1, 2, 3, 4))
        args = (self.params, *self.pools,
                *[_shaped((self.n_slots, width))
                  for width in self._table_widths(n_table)],
                self._tokens, self._positions, self._temps, self.rng,
                *self.state)
        at = len(self.pools) + len(self.allocators) - 1
        return self.executor.compile(
            f"{self.model.program_tag}-paged-decode-x{block}-NP{n_table}"
            f"{self._id_tag}",
            self._decode_fn_paged(block, n_table), args,
            donate_argnums=tuple(range(1, 1 + len(self.pools))) + tuple(
                range(at + 6, at + 6 + len(self.state))))

    # -- chunked prefill over the pool ---------------------------------------
    # A long prompt's chunks run against bucket-sized per-JOB temp caches
    # (per-layer [K, Hkv, dh, bucket] tuples carried in the job dict — the
    # same storage shape the fused paged prefill allocates internally), and
    # the FINAL chunk scatters the whole window into pages with the same
    # paged_write_prefill_stacked the fused path uses. Decode dispatches
    # interleave between chunks; a reserved-but-inactive slot's table row
    # is all zeros, so lock-step junk writes land in the garbage page by
    # construction.
    def _chunk_fn_paged(self, chunk: int, K: int, final: bool):
        cfg, mesh = self.cfg, self.mesh
        jnp = self._jnp
        top_k = self.top_k
        from ..models.llama import llama_prefill_chunk
        from .sampling import sample_tokens

        def forward(params, tmp_k, tmp_v, ctokens, cpositions, lengths,
                    start, selected):
            tmp_k = tuple(_pin_standard_layout(t) for t in tmp_k)
            tmp_v = tuple(_pin_standard_layout(t) for t in tmp_v)
            logits, tmp_k, tmp_v = llama_prefill_chunk(
                params, cfg, ctokens, cpositions, tmp_k, tmp_v,
                jnp.arange(K, dtype=jnp.int32),
                project_last=jnp.clip(lengths - 1 - start, 0, chunk - 1),
                mesh=mesh)
            in_chunk = ((lengths - 1 >= start)
                        & (lengths - 1 < start + chunk))       # [K]
            selected = jnp.where(in_chunk[:, None], logits, selected)
            return tmp_k, tmp_v, selected

        if not final:
            def run_chunk(params, tmp_k, tmp_v, ctokens, cpositions,
                          lengths, start, selected):
                tmp_k, tmp_v, selected = forward(
                    params, tmp_k, tmp_v, ctokens, cpositions, lengths,
                    start, selected)
                return tmp_k, tmp_v, selected

            return run_chunk

        def run_final(params, k_pool, v_pool, tmp_k, tmp_v, ctokens,
                      cpositions, ptable, slots, lengths, start, selected,
                      tokens, positions, temps, new_temps, rng):
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            tmp_k, tmp_v, selected = forward(
                params, tmp_k, tmp_v, ctokens, cpositions, lengths, start,
                selected)
            k_pool, v_pool = paged_write_prefill_stacked(
                k_pool, v_pool, jnp.stack(tmp_k), jnp.stack(tmp_v),
                ptable, lengths)
            first_tok, rng = sample_tokens(selected, rng, new_temps,
                                           top_k=top_k)
            tokens = tokens.at[slots].set(first_tok)
            positions = positions.at[slots].set(lengths)
            temps = temps.at[slots].set(new_temps)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return k_pool, v_pool, tokens, positions, temps, rng, first_tok

        return run_final

    def _chunk_fn_paged_q8_final(self, chunk: int, K: int):
        """Final chunk into INT8 pools: the whole full-precision temp
        window quantizes ONCE at the scatter (per token/head scales) —
        mid-chunks read full-precision temps, as the fused path does."""
        cfg = self.cfg
        jnp = self._jnp
        top_k = self.top_k
        from .sampling import sample_tokens

        base = self._chunk_fn_paged(chunk, K, final=False)

        def run_final(params, k_pool, v_pool, k_scale, v_scale, tmp_k,
                      tmp_v, ctokens, cpositions, ptable, slots, lengths,
                      start, selected, tokens, positions, temps, new_temps,
                      rng):
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            tmp_k, tmp_v, selected = base(
                params, tmp_k, tmp_v, ctokens, cpositions, lengths, start,
                selected)
            k8, ks = quantize_kv(jnp.stack(tmp_k), axis=-2)
            v8, vs = quantize_kv(jnp.stack(tmp_v), axis=-2)
            k_pool, v_pool = paged_write_prefill_stacked(
                k_pool, v_pool, k8, v8, ptable, lengths)
            k_scale = paged_write_prefill_scales(k_scale, ks, ptable, lengths)
            v_scale = paged_write_prefill_scales(v_scale, vs, ptable, lengths)
            first_tok, rng = sample_tokens(selected, rng, new_temps,
                                           top_k=top_k)
            tokens = tokens.at[slots].set(first_tok)
            positions = positions.at[slots].set(lengths)
            temps = temps.at[slots].set(new_temps)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return (k_pool, v_pool, k_scale, v_scale, tokens, positions,
                    temps, rng, first_tok)

        return run_final

    @program_lookup
    def _chunk_program_paged(self, chunk: int, K: int, bucket: int,
                             final: bool):
        """Chunk programs key on (chunk, K) and on the BUCKET (the temp
        caches are bucket-wide); buckets above the chunk size are few, so
        the compile set stays bounded."""
        from ..models.blocks import np_dtype

        Hkv, dh = self.cfg.n_kv_heads, self.cfg.head_dim
        L = self.cfg.n_layers
        dt = np_dtype(self.cfg.dtype)
        tmp = tuple(_shaped((K, Hkv, dh, bucket), dt) for _ in range(L))
        common = (_shaped((K, chunk)), _shaped((K, chunk)))
        selected = _shaped((K, self.cfg.vocab_size), np.float32)
        if not final:
            args = (self.params, tmp, tmp, *common,
                    _shaped((K,)), _shaped(()), selected)
            return self.executor.compile(
                f"llama-paged-chunk-{chunk}x{K}-b{bucket}{self._id_tag}",
                self._chunk_fn_paged(chunk, K, final=False), args,
                donate_argnums=(1, 2, 7))
        n_ptable = max(1, math.ceil(bucket / self.page_size))
        tail = (_shaped((K, n_ptable)), _shaped((K,)), _shaped((K,)),
                _shaped(()), selected,
                self._tokens, self._positions, self._temps,
                _shaped(self._temps_shape(K), np.float32), self.rng)
        if self._q8:
            args = (self.params, self.k_cache, self.v_cache, self.k_scale,
                    self.v_scale, tmp, tmp, *common, *tail)
            return self.executor.compile(
                f"llama-paged-chunk-q8-final-{chunk}x{K}-b{bucket}"
                f"{self._id_tag}",
                self._chunk_fn_paged_q8_final(chunk, K), args,
                donate_argnums=(1, 2, 3, 4, 5, 6, 13, 14, 15, 16))
        args = (self.params, self.k_cache, self.v_cache, tmp, tmp,
                *common, *tail)
        return self.executor.compile(
            f"llama-paged-chunk-final-{chunk}x{K}-b{bucket}{self._id_tag}",
            self._chunk_fn_paged(chunk, K, final=True), args,
            donate_argnums=(1, 2, 3, 4, 11, 12, 13, 14))

    def _start_chunk_job(self, bucket: int, slots_idx: List[int],
                         batch: List[GenerationRequest]) -> None:
        import time as _time

        jnp = self._jnp
        from ..models.blocks import np_dtype

        with self.steps.seg("host_prep"):
            ptokens, lengths, new_temps = self._prep_admission(bucket, batch)
            K = len(batch)
            n_ptable = max(1, math.ceil(bucket / self.page_size))
            ptable = np.zeros((K, n_ptable), dtype=np.int32)
            for row, request in enumerate(batch):
                pages = self._reservations.get(request.id)
                if pages is None:  # direct submit path outside _admit (tests)
                    pages = self.allocator.alloc(self._request_pages(request))
                    if pages is None:
                        raise RuntimeError("page pool exhausted at dispatch")
                    self._reservations[request.id] = pages
                prompt_pages = pages[:n_ptable]
                ptable[row, :len(prompt_pages)] = prompt_pages
            Hkv, dh = self.cfg.n_kv_heads, self.cfg.head_dim
            dt = np_dtype(self.cfg.dtype)
            tmp_shape = (K, Hkv, dh, bucket)

            def temp():
                t = tuple(jnp.zeros(tmp_shape, dtype=dt)
                          for _ in range(self.cfg.n_layers))
                if self.mesh is not None:
                    import jax
                    from jax.sharding import NamedSharding

                    from ..parallel.sharding import kv_cache_layer_spec

                    s = NamedSharding(self.mesh, kv_cache_layer_spec())
                    t = tuple(jax.device_put(b, s) for b in t)
                return t

            job = {
                "batch": batch, "slots_idx": slots_idx, "bucket": bucket,
                "chunk": self.chunk_prefill_tokens, "next_start": 0,
                "ptokens": np.asarray(ptokens), "lengths": lengths,
                "new_temps": new_temps, "ptable": ptable,
                "tmp_k": temp(), "tmp_v": temp(),
                "selected": jnp.zeros((K, self.cfg.vocab_size),
                                      dtype=jnp.float32),
            }
        self._dispatch_chunk(job)
        now = _time.monotonic()
        for row, request in enumerate(batch):
            request.admitted_at = now
            self._obs.hist("app_tpu_queue_wait_seconds",
                           now - request.enqueued_at)
            self.slots[slots_idx[row]].chunking = request
        self._chunk_jobs.append(job)

    def _dispatch_chunk(self, job) -> bool:
        jnp = self._jnp
        batch = job["batch"]
        K = len(batch)
        chunk = job["chunk"]
        start = job["next_start"]
        final = start + chunk >= job["bucket"]
        ctokens = job["ptokens"][:, start:start + chunk]
        cpositions = np.broadcast_to(
            np.arange(start, start + chunk, dtype=np.int32)[None, :],
            (K, chunk))
        program = self._chunk_program_paged(chunk, K, job["bucket"], final)
        self.steps.note_dispatch("chunk")
        try:
            with self.steps.seg("dispatch"):
                if self.faults is not None:
                    self.faults.hit("engine.chunk")
                if not final:
                    job["tmp_k"], job["tmp_v"], job["selected"] = program(
                        self.params, job["tmp_k"], job["tmp_v"],
                        jnp.asarray(ctokens), jnp.asarray(cpositions),
                        jnp.asarray(job["lengths"]),
                        jnp.asarray(start, dtype=jnp.int32), job["selected"])
                    first_tok = None
                elif self._q8:
                    (self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                     self._tokens, self._positions, self._temps, self.rng,
                     first_tok) = program(
                        self.params, self.k_cache, self.v_cache, self.k_scale,
                        self.v_scale, job["tmp_k"], job["tmp_v"],
                        jnp.asarray(ctokens), jnp.asarray(cpositions),
                        jnp.asarray(job["ptable"]),
                        jnp.asarray(np.asarray(job["slots_idx"],
                                               dtype=np.int32)),
                        jnp.asarray(job["lengths"]),
                        jnp.asarray(start, dtype=jnp.int32), job["selected"],
                        self._tokens, self._positions, self._temps,
                        jnp.asarray(job["new_temps"]), self.rng)
                else:
                    (self.k_cache, self.v_cache, self._tokens,
                     self._positions, self._temps, self.rng,
                     first_tok) = program(
                        self.params, self.k_cache, self.v_cache, job["tmp_k"],
                        job["tmp_v"], jnp.asarray(ctokens),
                        jnp.asarray(cpositions), jnp.asarray(job["ptable"]),
                        jnp.asarray(np.asarray(job["slots_idx"],
                                               dtype=np.int32)),
                        jnp.asarray(job["lengths"]),
                        jnp.asarray(start, dtype=jnp.int32), job["selected"],
                        self._tokens, self._positions, self._temps,
                        jnp.asarray(job["new_temps"]), self.rng)
        except Exception as exc:
            raise CacheLostError(
                f"paged chunk prefill dispatch failed: {exc}") from exc
        job["next_start"] = start + chunk
        job["first_tok"] = first_tok
        return final

    def _finish_chunk_job(self, job) -> None:
        super()._finish_chunk_job(job)
        # chunk-routed requests always dropped their hit (_admission_bucket)
        # but their freshly-written pages still INSERT, so the next request
        # with this prefix admits tail-only
        with self.steps.seg("bind"):
            self._assign_pages(job["slots_idx"], job["batch"])

    def _abort_chunk_job(self, job, exc) -> None:
        for request in job["batch"]:
            self._abort_admission(request)
        super()._abort_chunk_job(job, exc)

    # -- speculative decoding over the pool -----------------------------------
    def _verify_fn_paged(self, d: int, n_table: int):
        """The paged window forward (llama_verify_step_paged) around the
        acceptance epilogue (engine.spec_accept_epilogue)."""
        cfg, mesh = self.cfg, self.mesh
        top_k = self.top_k
        from ..models.llama import llama_verify_step_paged
        from .engine import spec_accept_epilogue

        def verify(params, k_pool, v_pool, table, tokens, positions, temps,
                   rng, drafts, draft_lens):
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            g, logits0, k_pool, v_pool = llama_verify_step_paged(
                params, cfg, tokens, drafts, positions, k_pool, v_pool,
                table, mesh)
            tokens, positions, rng, out, n_emit = spec_accept_epilogue(
                g, logits0, temps, rng, drafts, draft_lens, positions, d,
                top_k)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return (k_pool, v_pool, tokens, positions, rng, out, n_emit)

        return verify

    @program_lookup
    def _verify_program(self, n_table: int):
        d = self.speculative_tokens
        args = (self.params, self.k_cache, self.v_cache,
                _shaped((self.n_slots, n_table)),
                self._tokens, self._positions, self._temps, self.rng,
                _shaped((self.n_slots, d)), _shaped((self.n_slots,)))
        name = f"llama-paged-verify-x{d}-NP{n_table}{self._id_tag}"
        return self.executor.compile(name, self._verify_fn_paged(d, n_table),
                                     args, donate_argnums=(1, 2))

    def _verify_call(self, drafts, lens):
        jnp = self._jnp
        with self.steps.seg("host_prep"):
            table = self._build_table()
        program = self._verify_program(table.shape[1])
        (self.k_cache, self.v_cache, self._tokens, self._positions,
         self.rng, out_tokens, n_emit) = program(
            self.params, self.k_cache, self.v_cache, jnp.asarray(table),
            self._tokens, self._positions, self._temps, self.rng,
            drafts, lens)
        return out_tokens, n_emit

    # -- prefix-cache prefill (tail-only admission) ---------------------------
    def _prefix_fn(self, bucket: int, K: int, n_table: int):
        cfg = self.cfg
        jnp = self._jnp
        top_k = self.top_k
        from ..models.llama import llama_prefill_paged_prefix
        from .sampling import sample_tokens

        def prefill(params, k_pool, v_pool, ptokens, ptable, prefix_lens,
                    slots, lengths, tokens, positions, temps, new_temps,
                    rng):
            """Tail-only K-way admission: rows' shared prefix pages are
            already live in the pool; only the [K, bucket] tail window is
            computed and written (llama_prefill_paged_prefix), then first
            tokens sample and loop state splices exactly like the fused
            path."""
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            project_last = jnp.clip(lengths - prefix_lens - 1, 0,
                                    bucket - 1)
            last, k_pool, v_pool = llama_prefill_paged_prefix(
                params, cfg, ptokens, prefix_lens, lengths, k_pool, v_pool,
                ptable, project_last)
            first, rng = sample_tokens(last, rng, new_temps, top_k=top_k)
            tokens = tokens.at[slots].set(first)
            positions = positions.at[slots].set(lengths)
            temps = temps.at[slots].set(new_temps)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return k_pool, v_pool, tokens, positions, temps, rng, first

        return prefill

    def _prefix_fn_q8(self, bucket: int, K: int, n_table: int):
        """MIRRORS _prefix_fn over int8 pools + scale pools (the tail
        quantizes on write; the gathered read dequantizes — see
        llama_prefill_paged_prefix_q8)."""
        cfg = self.cfg
        jnp = self._jnp
        top_k = self.top_k
        from ..models.llama import llama_prefill_paged_prefix_q8
        from .sampling import sample_tokens

        def prefill(params, k_pool, v_pool, k_scale, v_scale, ptokens,
                    ptable, prefix_lens, slots, lengths, tokens, positions,
                    temps, new_temps, rng):
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            project_last = jnp.clip(lengths - prefix_lens - 1, 0,
                                    bucket - 1)
            (last, k_pool, v_pool, k_scale,
             v_scale) = llama_prefill_paged_prefix_q8(
                params, cfg, ptokens, prefix_lens, lengths, k_pool, v_pool,
                k_scale, v_scale, ptable, project_last)
            first, rng = sample_tokens(last, rng, new_temps, top_k=top_k)
            tokens = tokens.at[slots].set(first)
            positions = positions.at[slots].set(lengths)
            temps = temps.at[slots].set(new_temps)
            k_pool, v_pool = _pin_standard_layout(k_pool, v_pool)
            return (k_pool, v_pool, k_scale, v_scale, tokens, positions,
                    temps, rng, first)

        return prefill

    @program_lookup
    def _prefix_program(self, bucket: int, K: int, n_table: int):
        common = (_shaped((K, bucket)), _shaped((K, n_table)),
                  _shaped((K,)), _shaped((K,)), _shaped((K,)),
                  self._tokens, self._positions, self._temps,
                  _shaped(self._temps_shape(K), np.float32), self.rng)
        if self._q8:
            args = (self.params, self.k_cache, self.v_cache, self.k_scale,
                    self.v_scale, *common)
            return self.executor.compile(
                f"llama-paged-prefix-q8-{bucket}x{K}-NP{n_table}"
                f"{self._id_tag}",
                self._prefix_fn_q8(bucket, K, n_table),
                args, donate_argnums=(1, 2, 3, 4, 10, 11, 12))
        args = (self.params, self.k_cache, self.v_cache, *common)
        return self.executor.compile(
            f"llama-paged-prefix-{bucket}x{K}-NP{n_table}{self._id_tag}",
            self._prefix_fn(bucket, K, n_table),
            args, donate_argnums=(1, 2, 8, 9, 10))

    def _dispatch_prefill_prefix(self, bucket: int, slots_idx: List[int],
                                 batch: List[GenerationRequest],
                                 hits: List[List[int]]) -> None:
        jnp = self._jnp
        ps = self.page_size
        from .. import native

        K = len(batch)
        with self.steps.seg("host_prep"):
            prefix_lens = np.asarray([len(h) * ps for h in hits],
                                     dtype=np.int32)
            lengths = np.asarray([len(r.resume_tokens) for r in batch],
                                 dtype=np.int32)
            tails = [r.resume_tokens[len(h) * ps:]
                     for r, h in zip(batch, hits)]
            ptokens = native.pad_batch(tails, bucket)
            if ptokens is None:
                ptokens = np.zeros((K, bucket), dtype=np.int32)
                for row, tail in enumerate(tails):
                    ptokens[row, :len(tail)] = tail
            if self.sampling_controls:
                from .sampling import pack_controls

                new_temps = pack_controls([r.temperature for r in batch],
                                          [r.top_p for r in batch],
                                          [r.top_k for r in batch])
            else:
                new_temps = np.asarray([r.temperature for r in batch],
                                       dtype=np.float32)
            # table: shared prefix pages then the reservation's fresh pages,
            # wide enough for every row's full PROMPT page span
            n_table = _pow2_at_least(
                max(self.allocator.pages_for(int(n)) for n in lengths))
            ptable = np.zeros((K, n_table), dtype=np.int32)
            for row, request in enumerate(batch):
                pages = self._reservations.get(request.id)
                if pages is None:  # direct submit path outside _admit (tests)
                    pages = self.allocator.alloc(
                        self._request_pages(request) - len(hits[row]))
                    if pages is None:
                        raise RuntimeError("page pool exhausted at dispatch")
                    self._reservations[request.id] = pages
                combined = (hits[row] + pages)[:n_table]
                ptable[row, :len(combined)] = combined

        program = self._prefix_program(bucket, K, n_table)
        self.steps.note_dispatch("prefill")
        try:
            with self.steps.seg("dispatch"):
                if self.faults is not None:
                    self.faults.hit("engine.prefill")
                if self._q8:
                    (self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                     self._tokens, self._positions, self._temps, self.rng,
                     first) = program(
                        self.params, self.k_cache, self.v_cache, self.k_scale,
                        self.v_scale, jnp.asarray(ptokens),
                        jnp.asarray(ptable), jnp.asarray(prefix_lens),
                        jnp.asarray(np.asarray(slots_idx, dtype=np.int32)),
                        jnp.asarray(lengths), self._tokens, self._positions,
                        self._temps, jnp.asarray(new_temps), self.rng)
                else:
                    (self.k_cache, self.v_cache, self._tokens,
                     self._positions, self._temps, self.rng, first) = program(
                        self.params, self.k_cache, self.v_cache,
                        jnp.asarray(ptokens), jnp.asarray(ptable),
                        jnp.asarray(prefix_lens),
                        jnp.asarray(np.asarray(slots_idx, dtype=np.int32)),
                        jnp.asarray(lengths), self._tokens, self._positions,
                        self._temps, jnp.asarray(new_temps), self.rng)
        except Exception as exc:
            raise CacheLostError(
                f"prefix prefill dispatch failed: {exc}") from exc

        with self.steps.seg("bind"):
            batch_id = next(self._batch_seq)
            dspan = self._dispatch_span(
                "tpu.prefill", batch_id,
                **{"batch.size": K, "tpu.prefill_bucket": bucket,
                   "tpu.prefix_pages": int(prefix_lens.sum()) // ps})
            self._bind_slots(slots_idx, batch, first, bucket, batch_id, dspan)
            self._assign_pages(slots_idx, batch)

    def _assign_pages(self, slots_idx: List[int],
                      batch: List[GenerationRequest]) -> None:
        """Move each request's pages onto its slot (shared prefix pages
        first — table order) and register the freshly-written full prompt
        pages in the prefix cache."""
        for row, request in enumerate(batch):
            fresh = self._reservations.pop(request.id)
            shared = (self._prefix_hits.pop(request.id, None) or []
                      if self.prefix is not None else [])
            slot = self.slots[slots_idx[row]]
            slot.pages = list(shared) + fresh
            slot.more_pages = self._more_reservations.pop(request.id, None)
            if self.prefix is not None:
                self.prefix.insert(request.resume_tokens, slot.pages)

    # -- disaggregated hand-off (tpu/disagg.py) -------------------------------
    @loop_only
    def _handoff_slot(self, slot, request) -> None:
        """Prefill-pool KV export: gather the slot's prompt pages to the
        host (the spill path's async-overlap D2H), wrap them as PageBlobs,
        and give the stream to the hand-off sink; then evacuate the slot
        WITHOUT a terminal None — the decode pool owns the stream now.

        Runs on the loop thread at prefill sync, right after the first
        token was emitted (this pool's whole TTFT job). If the sink raises
        even for a blob-less fallback, the slot stays bound and decode
        continues locally, colocated-style — degraded, never dropped."""
        if self._handoff_sink is None:
            # bare prefill-role engine with no worker wired (tests): keep
            # the slot; decode runs locally
            return
        import time as _time

        from .kvtier import PageBlob

        ps = self.page_size
        n_ctx = slot.length          # positions whose KV the pages hold:
        window = request.resume_tokens[:n_ctx]   # the bound resume window
        n_kv = self.allocator.pages_for(n_ctx)
        handled, delivered = True, False
        try:
            with self.steps.seg("kv_handoff"):
                ids = np.asarray(slot.pages[:n_kv], dtype=np.int32)
                pulls = [self.k_cache[:, ids], self.v_cache[:, ids]]
                if self._q8:
                    pulls += [self.k_scale[:, ids], self.v_scale[:, ids]]
                host = self._fetch_host(*pulls)
                k, v = host[0], host[1]
                ks, vs = (host[2], host[3]) if self._q8 else (None, None)
                blobs = []
                for i in range(n_kv):
                    # tokens carry only the covered positions (the last
                    # page is usually partial): the decode pool's content
                    # verify reconcatenates them against its resume window
                    blobs.append(PageBlob(
                        tuple(window[i * ps:(i + 1) * ps]),
                        k[:, i], v[:, i],
                        None if ks is None else ks[:, i],
                        None if vs is None else vs[:, i]))
                delivered = bool(self._handoff_sink(request, blobs, n_ctx))
        except Exception:  # noqa: BLE001 - losing the export must not lose
            # the stream: offer the sink a blob-less hand-off (decode-pool
            # recompute of the resume window)
            try:
                delivered = bool(self._handoff_sink(request, None, n_ctx))
            except Exception:  # noqa: BLE001
                handled = False
        if not handled:
            return  # slot stays bound: local decode is the last resort
        if delivered:
            self.handoffs_total += 1
            self._obs.counter("app_tpu_disagg_handoffs_total")
        else:
            # the sink took ownership but already arranged its own
            # fallback (bounded queue full, decode pool shedding, ...)
            self.handoff_fallbacks_total += 1
            self._obs.counter("app_tpu_disagg_fallback_total",
                              reason="export")
        # evacuate exactly like _finish_slot, minus the terminal None
        self._release_slot_pages(slot)
        slot.request = None
        slot.length = 0
        slot.remaining = 0
        slot.history = None
        if self.sampling_controls and (request.top_p or request.top_k):
            idx = next((i for i, s in enumerate(self.slots) if s is slot),
                       None)
            if idx is not None:
                self._temps = self._temps.at[idx].set(0.0)
        request.finished_at = _time.monotonic()
        active_now = sum(1 for s in self.slots if s.active)
        used, free = self.allocator.used_pages, self.allocator.free_pages

        def job() -> None:
            if request.gen_span is not None:
                request.gen_span.set_attribute("tpu.tokens",
                                               request.generated)
                request.gen_span.set_attribute("disagg.handoff", True)
                request.gen_span.end()
            if self.recorder is not None:
                self.recorder.record_finished(request, "handoff")
            self._obs.gauge("app_tpu_active_slots", active_now)
            self._obs.gauge("app_tpu_pages_used", used)
            self._obs.gauge("app_tpu_kv_pool_pages", used, kind="used")
            self._obs.gauge("app_tpu_kv_pool_pages", free, kind="free")

        self._run_off_loop(job)

    def _admit_handoff(self, batch, free_iter, dispatched) -> None:
        """Decode-pool hand-off admission: validate each request's blobs
        against THIS pool (shape/dtype/scale presence plus token-content
        verify), land the whole wave's pages in one donated H2D scatter,
        and splice loop state so the next decode block simply continues
        the stream — no prefill dispatch, ever, on this pool. Any blob
        that fails verification degrades that request to a re-parked
        recompute (_handoff_fallback), mirroring the tier-restore guard
        in _restore_from_tier."""
        import time as _time

        jnp = self._jnp
        ps = self.page_size
        L, _, Hkv, dh, _ = self.k_cache.shape
        pool_dt = np.dtype(self.k_cache.dtype)
        ready = []
        with self.steps.seg("kv_handoff"):
            for request in batch:
                blobs = request.handoff_blobs
                # KV covers the resume window MINUS the last emitted token
                # (its KV is written by this pool's first decode step) —
                # the exact state a colocated slot has post-prefill-emit
                window = request.resume_tokens[:-1]
                n_ctx = len(window)
                reason = None
                if len(blobs) != self.allocator.pages_for(n_ctx):
                    reason = "page_count"
                else:
                    covered = []
                    for blob in blobs:
                        if (blob.k.shape != (L, Hkv, dh, ps)
                                or blob.k.dtype != pool_dt
                                or (self._q8 and blob.k_scale is None)):
                            reason = "shape"
                            break
                        covered.extend(blob.tokens)
                    if reason is None and covered != list(window):
                        reason = "content"
                if reason is not None:
                    self._handoff_fallback(request, reason)
                    dispatched.add(request.id)  # parked, not failed: the
                    continue  # caller's except-cleanup must skip it
                ready.append(request)
            if not ready:
                return
            # one pow2-padded donated scatter lands the whole wave; blobs
            # restore into the HEAD of each reservation (decode growth
            # continues into the tail pages)
            pages_all, blobs_all = [], []
            for request in ready:
                n_kv = len(request.handoff_blobs)
                pages_all.extend(self._reservations[request.id][:n_kv])
                blobs_all.extend(request.handoff_blobs)
            try:
                self._h2d_restore(pages_all, blobs_all)
            except Exception:  # noqa: BLE001 - restore is recoverable by
                # recompute; a real device loss resurfaces at dispatch
                for request in ready:
                    self._handoff_fallback(request, "restore")
                    dispatched.add(request.id)
                return
        with self.steps.seg("host_prep"):
            if self.sampling_controls:
                from .sampling import pack_controls

                new_temps = pack_controls([r.temperature for r in ready],
                                          [r.top_p for r in ready],
                                          [r.top_k for r in ready])
            else:
                new_temps = np.asarray([r.temperature for r in ready],
                                       dtype=np.float32)
            batch_id = next(self._batch_seq)
            now = _time.monotonic()
            idxs, last_toks, lengths = [], [], []
            for request in ready:
                slot_idx = next(free_iter)
                slot = self.slots[slot_idx]
                n_kv = len(request.handoff_blobs)
                slot.request = request
                slot.length = len(request.resume_tokens) - 1
                # budget counts EMISSIONS and the prefill pool's emissions
                # already moved into `generated` (no -1: nothing emits at
                # this bind — compare _bind_slots, whose -1 pre-pays the
                # prefill sync's first token)
                slot.remaining = request.max_new_tokens - request.generated
                slot.pages = self._reservations.pop(request.id)
                slot.history = (list(request.resume_tokens)
                                if self.speculative_tokens else None)
                request.handoff_blobs = None   # free the host copies
                request.admitted_at = now
                self._obs.hist("app_tpu_queue_wait_seconds",
                               now - request.enqueued_at)
                idxs.append(slot_idx)
                last_toks.append(request.resume_tokens[-1])
                lengths.append(slot.length)
                for span in (request.span, request.gen_span):
                    if span is not None:
                        span.set_attribute("batch.id", batch_id)
                        span.set_attribute("tpu.slot", slot_idx)
                if self.recorder is not None:
                    self.recorder.record_admitted(request, slot_idx, 0,
                                                  batch_id=batch_id)
                    self.recorder.record_event(request.id, "kv_handoff",
                                               pages=n_kv)
                dispatched.add(request.id)
        # splice loop state (eager scatters, off the decode hot loop): the
        # next decode block feeds each slot its last emitted token at the
        # position right after its restored KV — identical device state to
        # a colocated slot that just emitted its first token
        sl = jnp.asarray(np.asarray(idxs, dtype=np.int32))
        self._tokens = self._tokens.at[sl].set(
            jnp.asarray(np.asarray(last_toks, dtype=np.int32)))
        self._positions = self._positions.at[sl].set(
            jnp.asarray(np.asarray(lengths, dtype=np.int32)))
        self._temps = self._temps.at[sl].set(jnp.asarray(new_temps))

    # -- dispatch -------------------------------------------------------------
    def _note_model_counts(self, tokens_host, block: int) -> None:
        """Fold a synced decode block's counter rows (below the slots'
        token rows, column 0: the sum over the block's steps) into the
        engine's totals. No device access: the rows came with the tokens."""
        if len(self.model_counts):
            self.model_counts += tokens_host[self.n_slots:, 0]
            self.model_count_steps += block

    def _window_pages_used(self) -> int:
        return sum(allocator.used_pages for group, allocator in zip(
            self.model.groups, self.allocators) if group.window is not None)

    def _note_page_writes(self, live, block: int) -> int:
        """Count a synced decode block's page writes from what the host
        holds, no device access: a live row's `block` tokens began at its
        slot's length (pre-demux here: what the block found) and were
        placed by ONE write of its page, two where they crossed into the
        next; the int8 pools still write a page a token. Returns the
        block's page writes for the step ledger's record."""
        if self._q8:
            writes = block * len(live)
        else:
            ps = self.page_size
            writes = sum(1 + (self.slots[i].length % ps + block - 1) // ps
                         for i, _ in live)
        self.write_tokens += block * len(live)
        self.write_pages += writes
        return writes

    def _note_page_reads(self, live, block: int, n_table: int) -> None:
        """Count a synced decode block's folds from what the host holds,
        no device access: under a table `n_table` wide the read folds C
        pages a loop turn (ops/paged_attention `fold_of`, from the pools'
        shapes), and every step of the block, every attention layer, a
        live row's read walks the pages of its slot's length (pre-demux
        here: what the block found; the block's own tokens wait in its
        tail) in ceil(pages / C) folds, each C x page_size lanes but the
        row's last, which is computed as wide as the pages it copied
        (`_folds`). The int8 pools have no tail: step t attends the t + 1
        tokens written so far too. And how the kernel took its rows
        (`_groups`): R consecutive slots a grid step (`group_of`, from the
        pools' shapes and the slots), the slots without a request among
        them."""
        planes = len(self.model.planes)
        # (the planes a read walks: one with a stride is not among them)
        pools = ([self.k_cache, self.v_cache, self.k_scale, self.v_scale]
                 if self._q8 else [
                     self.pools[self._primary * planes + i]
                     for i, plane in enumerate(self.model.planes)
                     if plane.stride == 1])
        c, ps = fold_of(pools, n_table, self.mesh), self.page_size
        slots = np.asarray([i for i, _ in live], np.int64)
        held = np.zeros(len(self.slots), bool)
        held[slots] = True

        def groups(pools, width, pages, c, layers):
            every = np.zeros((len(held), block), np.int64)
            every[slots] = pages
            return layers * np.asarray(_groups(every, held, c, group_of(
                pools, width, len(held), self.mesh)), np.int64)

        tokens = np.asarray([self.slots[i].length for i, _ in live],
                            np.int64)[:, None]
        tokens = tokens + (np.arange(1, block + 1) if self._q8
                           else np.zeros(block, np.int64))
        pages = np.minimum(-(-tokens // ps), n_table)
        layers = self.model.groups[self._primary].layers
        folds, narrowed, computed = _folds(pages, c)
        self.read_folds += layers * folds
        self.read_narrowed += layers * narrowed
        self.read_lanes += layers * computed * ps
        self.read_tokens += layers * int(np.minimum(tokens, pages * ps).sum())
        self.read_pages_per_fold = c
        self.read_groups += groups(pools, n_table, pages, c, layers)
        # a window group's read walks from the page its lower bound is in:
        # step t's token at position length + t sees from length + t + 1
        # - window on, at most a ring of pages
        for index, read in self._more_reads.items():
            group, ring = self.model.groups[index], self._rings[index]
            pools = self.pools[index * planes:(index + 1) * planes]
            c = fold_of(pools, ring, self.mesh)
            lower = np.maximum(
                tokens + np.arange(1, block + 1) - group.window, 0)
            pages = np.clip(-(-tokens // ps) - lower // ps, 0, ring)
            seen = np.minimum(tokens, (lower // ps + pages) * ps) - lower
            folds, narrowed, computed = _folds(pages, c)
            read[0] += group.layers * folds
            read[1] += group.layers * int(np.maximum(seen, 0).sum())
            read[2] += group.layers * computed * ps
            read[3] = c
            read[4] += group.layers * narrowed
            read[5] += groups(pools, ring, pages, c, group.layers)

    def paging_snapshot(self) -> dict:
        """`/debug/engine` "paging". "write": how often the decode block's
        tail engages. `tokens_per_page_write` is 1.0 where every token
        rewrites its page, near the block size where a block is flushed
        once (14-16 at blocks of 16, 7-8 while requests wait and the half
        block runs). "read": how well the read's folds engage.
        `pages_per_fold` is C of the last decode block synced, `folds` the
        read kernel's loop turns (rows x steps x attention layers),
        `narrowed_folds` those of them computed under C pages wide (a
        row's last fold is as wide as the pages it copied, rounded up to
        a power of two: ops/paged_attention `fold_branch`), and
        `fold_live_share` the tokens attended in pages over the lanes the
        folds computed: what is left of 1.0 was masked (a half-filled last
        page, the pages a power of two adds; in a window group also the
        tokens of the walk's first page that lie before the lower
        bound). `short_row_share` is the share of the rows' reads that
        took the short rows' step (a grid step walks R consecutive slots,
        and where each holds one fold at most, takes tail and fold in one
        softmax step: ops/paged_attention `rows_a_step`), and
        `groups_split_share` the share of the groups read that walked row
        by row because one row was longer (`_groups`). "read" is
        the primary group's; "groups" has every page group: its blocks,
        its window, its pool's pages and how many are in use, the pages a
        sequence reserved there on average since the last reset, and its
        own "read"."""
        def read_of(folds, tokens, lanes, c, narrowed, groups):
            short, rows, split, read = (int(n) for n in groups)
            return {"pages_per_fold": c, "folds": folds,
                    "narrowed_folds": narrowed,
                    "fold_live_share": (round(tokens / lanes, 4)
                                        if lanes else None),
                    "short_row_share": (round(short / rows, 4)
                                        if rows else None),
                    "groups_split_share": (round(split / read, 4)
                                           if read else None)}

        groups = []
        for index, (group, allocator) in enumerate(zip(self.model.groups,
                                                       self.allocators)):
            primary = index == self._primary
            groups.append({
                "name": group.name, "layers": group.layers,
                "window": group.window, "pages": allocator.n_pages - 1,
                "used": allocator.used_pages,
                "reserved_per_sequence_mean": (
                    round(self._reserved_pages[index]
                          / self._reserved_sequences, 2)
                    if self._reserved_sequences else None),
                "read": (read_of(self.read_folds, self.read_tokens,
                                 self.read_lanes, self.read_pages_per_fold,
                                 self.read_narrowed, self.read_groups)
                         if primary else read_of(*self._more_reads[index]))})
        return {
            "write": {
                "tokens": self.write_tokens, "page_writes": self.write_pages,
                "tokens_per_page_write": (
                    round(self.write_tokens / self.write_pages, 3)
                    if self.write_pages else None)},
            "read": groups[self._primary]["read"],
            "groups": groups}

    def model_snapshot(self) -> dict:
        """`/debug/engine` "model": the family, the planes of its page
        and the bytes a token keeps in them, what it holds beside the
        pools, and what the family makes of its decode counters since the
        last reset (models/protocol.py `describe`)."""
        model = self.model
        counts = dict(zip(model.counters, self.model_counts.tolist()))
        return {"family": model.family,
                "kv_layers": model.kv_layers,
                "planes": [plane.describe() for plane in model.planes],
                "cache_bytes_per_token": (
                    model.token_values * self.pools[0].dtype.itemsize),
                "cache_bytes_per_sequence": (
                    model.sequence_values(self.max_seq_len, self.page_size)
                    * self.pools[0].dtype.itemsize),
                "state_bytes": self.state_bytes(),
                **model.describe(counts, self.model_count_steps)}

    def _build_table(self) -> np.ndarray:
        """Block table for the current active slots, padded to a power-of-
        two width with one extra garbage column (see _dispatch_decode)."""
        active = [(i, slot) for i, slot in enumerate(self.slots)
                  if slot.active]
        widest = max(len(slot.pages) for _, slot in active)
        n_table = _pow2_at_least(widest + 1)
        table = np.zeros((self.n_slots, n_table), dtype=np.int32)
        for i, slot in active:
            table[i, :len(slot.pages)] = slot.pages
        return table

    def _table_widths(self, n_table: int) -> List[int]:
        """The tables' widths, a group: `n_table` the primary group's, a
        window group's its ring (column j % ring holds logical page j: no
        garbage column, a position never falls off a ring)."""
        return [n_table if ring is None else ring for ring in self._rings]

    def _build_tables(self) -> List[np.ndarray]:
        """A block table a group: `_build_table`, and beside it each other
        group's pages in ring-column order, fixed for a request's life."""
        tables = [None] * len(self.allocators)
        tables[self._primary] = self._build_table()
        widths = self._table_widths(tables[self._primary].shape[1])
        for index, width in enumerate(widths):
            if index == self._primary:
                continue
            table = np.zeros((self.n_slots, width), dtype=np.int32)
            for i, slot in enumerate(self.slots):
                if slot.active:
                    pages = slot.more_pages[index]
                    table[i, :len(pages)] = pages
            tables[index] = table
        return tables

    def _prefill_tables(self, batch, n_ptable: int) -> List[np.ndarray]:
        """A [K, n_ptable] prefill table a group, column j the page of the
        prompt's tokens [j ps, (j + 1) ps). A window group's names the
        prompt's LAST ring of pages only, each at its ring column's page
        (j % ring), and the garbage page for what lies before them: the
        writer puts what a later token can still see."""
        K = len(batch)
        tables = []
        for index, allocator in enumerate(self.allocators):
            ring = self._rings[index]
            table = np.zeros((K, n_ptable), dtype=np.int32)
            for row, request in enumerate(batch):
                if index == self._primary:
                    pages = self._reservations[request.id][:n_ptable]
                    table[row, :len(pages)] = pages
                    continue
                pages = self._more_reservations[request.id][index]
                last = min(n_ptable, allocator.pages_for(
                    len(request.resume_tokens)))
                for j in range(max(0, last - ring), last):
                    table[row, j] = pages[j % ring]
            tables.append(table)
        return tables

    def _dispatch_prefill(self, bucket: int, slots_idx: List[int],
                          batch: List[GenerationRequest]) -> None:
        if self.prefix is not None:
            hits = [self._prefix_hits.get(r.id) or [] for r in batch]
            if any(hits):
                # `bucket` is already the group's TAIL bucket
                # (_admission_bucket); all-miss rows ride along with
                # prefix_len 0
                self._dispatch_prefill_prefix(bucket, slots_idx, batch,
                                              hits)
                return
        K = len(batch)
        jnp = self._jnp
        with self.steps.seg("host_prep"):
            ptokens, lengths, new_temps = self._prep_admission(bucket, batch)
            n_ptable = max(1, math.ceil(bucket / self.page_size))
            for request in batch:
                if request.id in self._reservations:
                    continue    # direct submit path outside _admit (tests)
                pages = self.allocator.alloc(self._request_pages(request))
                if pages is None:
                    raise RuntimeError("page pool exhausted at dispatch")
                self._reservations[request.id] = pages
                if not self._reserve_more(request):
                    raise RuntimeError("page pool exhausted at dispatch")
                self._note_reserved(request)
            ptables = self._prefill_tables(batch, n_ptable)
            ptable = ptables[self._primary]

        program = self._prefill_program(bucket, K)
        self.steps.note_dispatch("prefill")
        try:
            with self.steps.seg("dispatch"):
                if self.faults is not None:
                    self.faults.hit("engine.prefill")
                if self._q8:
                    (self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                     self._tokens, self._positions, self._temps, self.rng,
                     first) = program(
                        self.params, self.k_cache, self.v_cache, self.k_scale,
                        self.v_scale, jnp.asarray(ptokens),
                        jnp.asarray(ptable),
                        jnp.asarray(np.asarray(slots_idx, dtype=np.int32)),
                        jnp.asarray(lengths), self._tokens, self._positions,
                        self._temps, jnp.asarray(new_temps), self.rng)
                else:
                    out = program(
                        self.params, *self.pools,
                        jnp.asarray(ptokens),
                        *[jnp.asarray(table) for table in ptables],
                        jnp.asarray(np.asarray(slots_idx, dtype=np.int32)),
                        jnp.asarray(lengths), self._tokens, self._positions,
                        self._temps, jnp.asarray(new_temps), self.rng,
                        *self.state)
                    n = len(self.pools)
                    (self._tokens, self._positions, self._temps, self.rng,
                     first, *state) = out[n:]
                    self.pools, self.state = list(out[:n]), tuple(state)
        except Exception as exc:
            raise CacheLostError(f"paged prefill dispatch failed: {exc}") from exc

        with self.steps.seg("bind"):
            batch_id = next(self._batch_seq)
            dspan = self._dispatch_span("tpu.prefill", batch_id,
                                        **{"batch.size": K,
                                           "tpu.prefill_bucket": bucket})
            self._bind_slots(slots_idx, batch, first, bucket, batch_id, dspan)
            self._assign_pages(slots_idx, batch)

    def _dispatch_decode(self) -> None:
        import time as _time

        jnp = self._jnp
        # table width includes +1 garbage column: a speculative overrun
        # position clamps its page_slot to the LAST column, which must be
        # garbage (0) for every row so dead steps can never write into a
        # live page
        with self.steps.seg("host_prep"):
            tables = self._build_tables()
        table = tables[self._primary]
        n_table = table.shape[1]
        block = self._decode_block_now()
        program = self._decode_program_paged(n_table, block)
        snapshot = [(i, slot.request) for i, slot in enumerate(self.slots)
                    if slot.active]
        self.steps.note_dispatch("decode", steps=block)
        start = _time.monotonic()
        try:
            with self.steps.seg("dispatch"):
                if self.faults is not None:
                    self.faults.hit("engine.decode")
                if self._q8:
                    (self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                     self._tokens, self._positions, self.rng, out_tokens) = \
                        program(self.params, self.k_cache, self.v_cache,
                                self.k_scale, self.v_scale,
                                jnp.asarray(table), self._tokens,
                                self._positions, self._temps, self.rng)
                else:
                    out = program(
                        self.params, *self.pools,
                        *[jnp.asarray(table) for table in tables],
                        self._tokens, self._positions,
                        self._temps, self.rng, *self.state)
                    n = len(self.pools)
                    (self._tokens, self._positions, self.rng, out_tokens,
                     *state) = out[n:]
                    self.pools, self.state = list(out[:n]), tuple(state)
        except Exception as exc:
            raise CacheLostError(f"paged decode dispatch failed: {exc}") from exc
        self._start_d2h(out_tokens)
        dspan = self._dispatch_span("tpu.decode", next(self._batch_seq),
                                    **{"batch.size": len(snapshot),
                                       "tpu.block": block,
                                       "tpu.table_width": n_table})
        self._inflight.append(("decode", out_tokens, snapshot,
                               block, start, dspan, n_table))

    def _reset_device_state(self, exc: BaseException) -> None:
        # slot pages are NOT released individually: _init_device_state
        # (inside super()) rebuilds the allocator + prefix cache wholesale,
        # and replayed survivors re-reserve against the fresh pool at
        # re-admission (super holds the state lock; only the loop thread
        # touches _reservations, so clearing here is safe)
        self._reservations.clear()
        self._more_reservations.clear()
        self._prefix_hits.clear()
        super()._reset_device_state(exc)
