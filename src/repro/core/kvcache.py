"""Tiered KV-cache for long-context serving: paged vs log vs hybrid
(DESIGN.md §2a).

The TPU translation of the paper's question. Tiers: HBM (fast, small) ↔ host
DRAM over PCIe (big, bandwidth-asymmetric) ↔ disk (preempted sequences).
Every design is a :class:`repro.core.engines.kv.KVCacheEngine` plugin,
constructed from the same :class:`~repro.core.engines.EngineSpec` the FS
registry uses (``create_kv_engine(spec, kvspec, clock)``):

* ``paged``  (:class:`PagedKVCache`, NVPages): fixed-size token pages live
  in a host pool; a block table maps (seq, logical page) → physical page; an
  HBM LRU holds the working set; appends go through a redo buffer then into
  the page (2× write); misses DMA whole pages up. Attention over resident
  pages uses the ``paged_attention`` Pallas kernel's block-table layout.
* ``log``  (:class:`LogKVCache`, NVLog): appends go to one sequential host
  log (1× write); a per-sequence HBM hot-window holds the most recent tokens
  (the paper's small DRAM cache); a background drainer compacts log segments
  into host pages; cold reads patch pages from the log (``log_patch`` kernel
  layout).
* ``kvhybrid``  (:class:`HybridKVCache`): the serving twin of the FS
  ``nvhybrid`` engine. Appends route adaptively — small appends (decode
  tokens of hot sequences) take the log hot-window path, large appends
  (prefill bursts, restores of long cold sequences) go straight to pages —
  with the threshold learned online from the observed append-size/reuse
  histogram (:class:`AdaptiveRouter`). The log drains through per-shard
  parallel drainers (hash(seq) → shard, each shard an independent FIFO
  server on the shared ``SimClock``), and a shard force-drains before the
  page side takes ownership of a page — the same log-before-pages ordering
  as ``nvhybrid``.

Data movement is real (numpy); PCIe/HBM/disk timing is modeled via SimClock.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Iterable, Optional, Sequence

import numpy as np

from repro.core.clock import ShardedDrainer, SimClock
from repro.core.engines.base import EngineSpec
from repro.core.engines.desc import (CacheDescriptor, PLANE_STAT_NAMES,
                                     dense_descriptor)
from repro.core.engines.kv import KVCacheEngine, register_kv_engine
from repro.core.lru import LRUList
from repro.roofline.hw import SSD, TierSpec

# PCIe gen4 x16-ish host link as seen from the device, and HBM for reference
HOST_LINK = TierSpec("host", read_bw=16e9, write_bw=16e9,
                     rand_read_bw=4e9, rand_write_bw=4e9,
                     read_latency=5e-6, write_latency=5e-6)
HBM = TierSpec("hbm", read_bw=819e9, write_bw=819e9,
               rand_read_bw=400e9, rand_write_bw=400e9,
               read_latency=1e-6, write_latency=1e-6)


@dataclass
class KVSpec:
    num_layers: int
    kv_heads: int
    head_dim: int
    page_tokens: int = 16
    dtype: np.dtype = np.dtype(np.float16)
    #: optional cache descriptor (repro.core.engines.desc) naming the pool's
    #: planes; None resolves to the legacy dense (k, v) layout, so every
    #: mirror engine's byte math below is unchanged
    desc: Optional[CacheDescriptor] = None

    def descriptor(self) -> CacheDescriptor:
        if self.desc is not None:
            return self.desc
        return dense_descriptor(self.num_layers, self.kv_heads,
                                self.head_dim, self.page_tokens,
                                dtype=np.dtype(self.dtype).name)

    @property
    def token_bytes(self) -> int:          # K+V for one token, one layer
        return 2 * self.kv_heads * self.head_dim * self.dtype.itemsize

    @property
    def page_bytes(self) -> int:
        return self.page_tokens * self.token_bytes

    def empty_page(self) -> np.ndarray:
        return np.zeros((2, self.page_tokens, self.kv_heads, self.head_dim),
                        self.dtype)


class _TieredKV(KVCacheEngine):
    """Shared engine plumbing: batched appends, preempt/restore via the disk
    tier, and the preempted-sequence guard. Engines implement
    ``_append_tokens`` / ``_read`` / ``_drop_seq``."""

    def __init__(self, spec: KVSpec, clock: SimClock):
        self.spec = spec
        self.clock = clock
        self.seq_len: dict[int, int] = {}
        self._preempted: dict[int, np.ndarray] = {}   # seq → (L, 2, T, K, D)
        self.stats: dict = {"preempts": 0, "restores": 0, "releases": 0,
                            "preempt_out_bytes": 0, "restore_in_bytes": 0,
                            # prefix-sharing counters (ISSUE 6) — zero on
                            # engines without sharing so the stats key set
                            # stays identical across every registered engine
                            "prefix_hits": 0, "prefix_tokens_reused": 0,
                            "cow_copies": 0, "shared_pages": 0,
                            # async-tiering counters (ISSUE 8) — zero on
                            # engines without a transfer pipeline, same rule
                            "async_spills": 0, "prefetch_hits": 0,
                            "stall_ticks_saved": 0,
                            # fault-tolerance counters (ISSUE 10) — zero on
                            # engines without a pipeline or when no injector
                            # is attached, so the key set stays uniform
                            "transfer_retries": 0, "transfer_failures": 0,
                            "retried_faults": 0, "host_pages_lost": 0,
                            "shard_stalls": 0, "tiering_degraded": 0}
        # per-plane pool traffic (ISSUE 9) — one counter pair per plane in
        # the descriptor universe, zero on engines without a pool, so the
        # stats key set stays identical across every registered engine.
        # Paged-plane spills satisfy the exactness invariant per plane:
        # pool_d2h_bytes_<p> == pool_page_spills × plane_page_bytes(p).
        for plane in PLANE_STAT_NAMES:
            self.stats[f"pool_d2h_bytes_{plane}"] = 0
            self.stats[f"pool_h2d_bytes_{plane}"] = 0

    # hooks -----------------------------------------------------------------
    def _append_tokens(self, seq: int, toks: list[np.ndarray]) -> None:
        raise NotImplementedError

    def _read(self, seq: int, layer: int) -> np.ndarray:
        raise NotImplementedError

    def _drop_seq(self, seq: int) -> None:
        raise NotImplementedError

    def _spill(self, seq: int) -> np.ndarray:
        """Materialize ``(L, 2, T, K, D)`` for preemption WITHOUT the read
        path's side effects (no HBM LRU touches, DMA faults, or router
        reuse feedback) — preempting must not pollute what stays resident."""
        raise NotImplementedError

    # protocol --------------------------------------------------------------
    def _check_active(self, seq: int) -> None:
        if seq in self._preempted:
            raise RuntimeError(
                f"sequence {seq} is preempted to disk; restore() it first")

    def append(self, seq: int, kv_tokens: np.ndarray) -> None:
        self._check_active(seq)
        kv_tokens = np.asarray(kv_tokens)
        if kv_tokens.ndim == 4:            # (L, 2, K, D): one decoded token
            toks = [kv_tokens]
        elif kv_tokens.ndim == 5:          # (L, 2, T, K, D): prefill burst
            toks = [kv_tokens[:, :, t] for t in range(kv_tokens.shape[2])]
        else:
            raise ValueError(
                f"kv_tokens must be (L, 2, K, D) or (L, 2, T, K, D); got "
                f"shape {kv_tokens.shape}")
        if toks:
            self._append_tokens(seq, toks)

    def read(self, seq: int, layer: int) -> np.ndarray:
        self._check_active(seq)
        return self._read(seq, layer)

    def preempt(self, seq: int) -> None:
        self._check_active(seq)
        blob = self._spill(seq)
        # sequential drain of the whole sequence out of the host tier and
        # onto the disk tier (one streamed copy, no random faults)
        self.clock.charge(HOST_LINK, "read", blob.nbytes, random_access=False)
        self.clock.charge(SSD, "write", blob.nbytes, random_access=False)
        self._drop_seq(seq)
        self.seq_len.pop(seq, None)
        self._preempted[seq] = blob
        self.stats["preempts"] += 1
        self.stats["preempt_out_bytes"] += blob.nbytes

    def restore(self, seq: int) -> None:
        blob = self._preempted.pop(seq, None)
        if blob is None:
            raise RuntimeError(f"sequence {seq} is not preempted")
        self.clock.charge(SSD, "read", blob.nbytes, random_access=False)
        self.stats["restores"] += 1
        self.stats["restore_in_bytes"] += blob.nbytes
        toks = [blob[:, :, t] for t in range(blob.shape[2])]
        if toks:
            # restore re-enters through the append path: one large batch —
            # under kvhybrid a long cold sequence lands on the page side
            self._append_tokens(seq, toks)

    def _on_release(self, seq: int) -> None:
        """Hook: per-sequence policy-state cleanup on release (adaptive
        routers forget their reuse histograms here). Runs on BOTH release
        branches — active and preempted — so every engine forgets
        consistently (the kvhybrid-only forget was a leak)."""

    def release(self, seq: int) -> None:
        """Finished request: drop the sequence from every tier. A preempted
        sequence just drops its disk blob; an active one drops host/HBM
        state through the engine's ``_drop_seq``."""
        if self._preempted.pop(seq, None) is None:
            self._drop_seq(seq)
            self.seq_len.pop(seq, None)
        self.stats["releases"] += 1
        self._on_release(seq)


@register_kv_engine("paged")
class PagedKVCache(_TieredKV):
    """NVPages design over (layer, seq) KV pages.

    Two modes share the block table and the (seq → [phys]) indirection:

    * **host mode** (default, the original design): pages live in a host
      numpy pool, an HBM LRU models the device working set, appends pay the
      2× redo+page host write, misses DMA whole pages up.
    * **pooled mode** (:meth:`init_pool`, the mirror-free serving path):
      pages live in device-resident ``(L, P, T, K, D)`` arrays the
      paged_attention kernel reads directly. Page alloc/free is tied to the
      same LRU accounting — when the fixed pool fills, the least-recently
      -used page of a non-pinned sequence is *spilled to the host tier at
      page granularity* (D2H one page) and faulted back on demand (H2D),
      so HBM-pressure spills evict pool pages, never dense per-sequence
      mirrors. Decode appends are device-born (the model scatters them in
      place) and cost HBM writes only — zero device→host mirror traffic.
    """

    def __init__(self, spec: KVSpec, clock: SimClock, *,
                 hbm_budget_bytes: int, async_tiering: bool = False,
                 transfer_max_retries: int = 3,
                 transfer_backoff_s: float = 1e-4):
        super().__init__(spec, clock)
        self.pool: dict[tuple, np.ndarray] = {}      # (layer, phys) → page
        self.block_table: dict[int, list[int]] = {}  # seq → [phys per logical]
        self.hbm_lru = LRUList()                     # (layer, phys) resident
        self.hbm_budget_bytes = hbm_budget_bytes
        self.hbm_capacity = max(hbm_budget_bytes // spec.page_bytes, 1)
        self.next_phys = 0
        self._pooled = False
        self._share_index = None       # prefix index (set_share_index)
        self.async_tiering = bool(async_tiering)
        self._pipeline = None          # TransferPipeline once pooled + async
        self._injector = None          # FaultInjector (set_fault_injector)
        self._xfer_retries = transfer_max_retries
        self._xfer_backoff = transfer_backoff_s
        self.stats.update({"hbm_hits": 0, "hbm_misses": 0, "dma_up_bytes": 0,
                           "host_writes": 0, "redo_bytes": 0})

    @classmethod
    def from_spec(cls, spec: EngineSpec, kvspec: KVSpec,
                  clock: SimClock) -> "PagedKVCache":
        return cls(kvspec, clock, hbm_budget_bytes=spec.kv_hbm_bytes,
                   async_tiering=spec.async_tiering,
                   transfer_max_retries=spec.transfer_max_retries,
                   transfer_backoff_s=spec.transfer_backoff_s)

    # ------------------------------------------------------ device page pool
    def supports_pool(self) -> bool:
        return True

    @property
    def pooled(self) -> bool:
        return self._pooled

    def init_pool(self, dtype=None, pages: Optional[int] = None) -> None:
        import jax.numpy as jnp
        if self._pooled:
            raise RuntimeError("init_pool() called twice")
        if self.seq_len or self.pool or self._preempted:
            raise RuntimeError("init_pool() must run before any append")
        spec = self.spec
        desc = spec.descriptor()
        if dtype is not None:
            desc = desc.with_kv_dtype(dtype)
        if desc.page_tokens != spec.page_tokens:
            raise ValueError(
                f"descriptor page_tokens={desc.page_tokens} disagrees with "
                f"KVSpec page_tokens={spec.page_tokens}")
        self.desc = desc
        self._plane_names = tuple(p.name for p in desc.paged_planes)
        self._state_only = not desc.has_pages
        kv_planes = [p for p in desc.paged_planes if p.kind == "kv"]
        self.pool_dtype = (kv_planes[0].np_dtype if kv_planes
                           else np.dtype(np.float32))
        # one physical page spans every layer and every plane (the block
        # table is shared by the whole stack), so a page group costs L
        # per-layer pages of HBM summed across the descriptor's planes
        self._group_bytes = desc.page_group_bytes
        self.dev_planes: dict = {}
        if desc.has_pages:
            self.pool_pages = (pages if pages is not None else
                               max(self.hbm_budget_bytes
                                   // self._group_bytes, 1))
            for p in desc.paged_planes:
                shape = ((spec.num_layers, self.pool_pages, spec.page_tokens)
                         + tuple(p.shape))
                self.dev_planes[p.name] = jnp.zeros(shape, p.np_dtype)
        else:
            # state-only layout (SSM): zero paged planes — per-seq state
            # rows ride alongside the (empty) page tables instead, spilled
            # and restored whole with the row
            self.pool_pages = 0
            self._state_capacity = max(
                self.hbm_budget_bytes // max(desc.seq_state_bytes, 1), 1)
        self.seq_state: dict[int, dict] = {}     # seq → plane → (L, *shape)
        self.free_pages: list[int] = list(range(self.pool_pages - 1, -1, -1))
        self.pool_lru = LRUList()                    # resident phys pages
        # refcounted page users: phys → {seq: logical}. A page may appear in
        # several sequences' block tables at once (prefix sharing); it is
        # freed only when its user dict empties AND no index pin remains.
        self.page_users: dict[int, dict[int, int]] = {}
        self.trie_refs: set[int] = set()             # index-pinned pages
        # spilled pages: (seq, logical) → {plane → (L, T, *shape)}
        self.host_pages: dict[tuple[int, int], dict] = {}
        self._pooled = True
        # async tiering (ISSUE 8): spills/faults drain through a background
        # pipeline; the hot/cold victim model runs in BOTH modes so spill
        # decisions (and therefore tokens) are identical sync vs async.
        # Lazy import: serving owns the pipeline, importing it at module
        # scope would cycle through the serving package (same rule as
        # _cow_page's batching import).
        from repro.serving.tiering import PageHeat, TransferPipeline
        if self.async_tiering:
            self._pipeline = TransferPipeline(
                self.clock, stats=self.stats, injector=self._injector,
                max_retries=self._xfer_retries,
                backoff_s=self._xfer_backoff)
        self._heat = PageHeat()
        self._alloc_seq = 0            # allocation counter (logical time)
        self._fault_mark: dict[int, int] = {}   # phys → _alloc_seq at fault
        self.stats.update({"pool_appends": 0, "pool_hits": 0,
                           "pool_faults": 0, "pool_page_spills": 0,
                           "pool_d2h_bytes": 0, "pool_h2d_bytes": 0})

    def pool_views(self):
        """Device pool planes in descriptor order — dense descriptors
        return the classic ``(pool_k, pool_v)`` pair."""
        if not self._pooled:
            return super().pool_views()      # the loud "no pool" error
        return tuple(self.dev_planes[n] for n in self._plane_names)

    def _token_group_bytes(self) -> int:
        """One pooled token across all layers and planes."""
        return self.desc.token_group_bytes

    def _page_planes_np(self, phys: int) -> dict:
        """Materialize device page ``phys`` as host arrays, one
        ``(L, T, *shape)`` per plane (one host sync each)."""
        from repro.serving.trace import TRACER
        TRACER.count("host_syncs", len(self._plane_names))
        return {n: np.asarray(self.dev_planes[n][:, phys])
                for n in self._plane_names}

    def _count_plane_bytes(self, counter: str, page: dict) -> None:
        """Charge a page/blob's bytes to the per-plane traffic counters."""
        for name, arr in page.items():
            self.stats[f"{counter}_{name}"] += arr.nbytes

    def _touch_page(self, phys: int) -> None:
        """One page access: LRU recency + the hot/cold model's EMA."""
        self.pool_lru.touch(phys)
        self._heat.touch(phys)

    def _recently_faulted(self, phys: int) -> bool:
        """Was ``phys`` faulted within the last pool-size allocations?
        Such pages spill only as a last resort (ISSUE 8 thrash guard): a
        page that just paid an H2D round-trips straight back out otherwise.
        Allocation count, not wall time, so sync/async rank identically."""
        return (self._alloc_seq - self._fault_mark.get(phys, -self.pool_pages)
                <= self.pool_pages)

    def _spill_lru_page(self, pinned: set) -> int:
        """Evict one spillable resident page to the host tier (page-granular
        spill); returns the freed physical index.

        Refcount-aware (ISSUE 6): only a page with exactly ONE live user —
        and that user outside the pinned batch — can spill coherently;
        pages aliased by several sequences never spill (the scheduler
        preempts whole sequences instead). A single-user page the prefix
        index also pins is forgotten from the index first: the cache
        re-prefills on a future miss, no sequence loses data. A pin with NO
        index object behind it (raw ``pin_page`` use) is dropped instead of
        skipped — skipping made that page headroom the pressure surface
        promised but eviction could never deliver (ISSUE 8).

        Victim choice is no longer pure LRU (ISSUE 8): eligible candidates
        rank by ``(recently_faulted, hotness, LRU rank)`` — coldest page by
        the :class:`~repro.serving.tiering.PageHeat` re-reference model
        first, LRU order breaking ties, and just-faulted pages last so a
        multi-page fault burst cannot evict its own pages (thrash). Every
        page costs the same one-page H2D to miss on, so min re-reference
        probability IS min expected miss cost.

        Async mode submits the D2H to the background pipeline — the numpy
        copy below is the staging buffer, the link time drains beside the
        foreground, and only a reader of the host copy barriers on it."""
        best = None
        for rank, phys in enumerate(self.pool_lru.lru_order()):
            users = self.page_users.get(phys)
            if not users or len(users) > 1:
                continue               # index-only (reclaimed, not spilled)
                                       # or shared between live sequences
            (seq, logical), = users.items()
            if seq in pinned:
                continue
            # index-pinned single-user pages stay eligible: a live index
            # forgets them first, a stale pin (no index) just drops
            key = (self._recently_faulted(phys), self._heat.hotness(phys),
                   rank)
            if best is None or key < best[0]:
                best = (key, phys, seq, logical)
        if best is None:
            raise RuntimeError(
                "paged pool exhausted: every resident page is pinned, "
                "shared, or index-held — the HBM budget is too small for "
                "the running batch")
        _, phys, seq, logical = best
        if phys in self.trie_refs:
            if self._share_index is not None:
                self._share_index.forget_phys(phys)
            else:
                self.trie_refs.discard(phys)
        page = self._page_planes_np(phys)
        nbytes = sum(a.nbytes for a in page.values())
        self.host_pages[(seq, logical)] = page
        self.block_table[seq][logical] = -1
        self.page_users.pop(phys)
        self.pool_lru.remove(phys)
        if self._pipeline is not None and not self._pipeline.degraded:
            self._pipeline.submit(self._pipeline.D2H, ("d2h", seq, logical),
                                  HOST_LINK, "write", nbytes)
            self.stats["async_spills"] += 1
            self.stats["stall_ticks_saved"] += 1   # sync stalls right here
        else:
            # no pipeline, or terminal transfer faults flipped it to
            # degraded: synchronous tiering on the foreground clock
            self.clock.charge(HOST_LINK, "write", nbytes,
                              random_access=True)          # D2H page out
        self.stats["pool_page_spills"] += 1
        self.stats["pool_d2h_bytes"] += nbytes
        self._count_plane_bytes("pool_d2h_bytes", page)
        return phys

    def _alloc_page(self, pinned: set) -> int:
        self._alloc_seq += 1
        if self.free_pages:
            return self.free_pages.pop()
        # reclaim before spilling: an idle index-held page (no live user)
        # frees without any D2H traffic — dropping cached prefix KV is
        # cheaper than spilling a live sequence's page
        if self._share_index is not None:
            if self._share_index.reclaim_one() is not None:
                return self.free_pages.pop()
        else:
            # pins without an index object cannot reclaim through the index;
            # free an idle one directly so the headroom the pressure surface
            # counted actually exists at allocation time (ISSUE 8)
            idle = next((p for p in sorted(self.trie_refs)
                         if not self.page_users.get(p)), None)
            if idle is not None:
                self.trie_refs.discard(idle)
                self.page_users.pop(idle, None)
                if idle in self.pool_lru:
                    self.pool_lru.remove(idle)
                return idle
        return self._spill_lru_page(pinned)

    def _extend_table(self, seq: int, pinned: set) -> None:
        table = self.block_table.setdefault(seq, [])
        phys = self._alloc_page(pinned)
        self.page_users[phys] = {seq: len(table)}
        table.append(phys)
        self._heat.assign(phys)
        self._touch_page(phys)

    def _fault_page(self, seq: int, logical: int, pinned: set) -> None:
        import jax.numpy as jnp
        if self._injector is not None \
                and self._injector.page_lost(seq, logical):
            # the spilled host copy is gone (ISSUE 10): surface the loss
            # BEFORE any allocation side effect so there is nothing to
            # unwind — the scheduler sheds this row back to waiting and
            # re-prefills it (degradation, never token divergence)
            from repro.serving.faults import LostPageError
            if self._pipeline is not None:
                self._pipeline.cancel(("d2h", seq, logical), reclaim=True)
                self._pipeline.cancel(("h2d", seq, logical), reclaim=True)
            self.host_pages.pop((seq, logical), None)
            self.stats["host_pages_lost"] += 1
            raise LostPageError(seq, logical)
        phys = self._alloc_page(pinned)
        prefetched = False
        retried = False
        pipe = self._pipeline
        use_async = pipe is not None and not pipe.degraded
        if pipe is not None:
            # coherence: the H2D reads the host staging copy, so it chains
            # after the page's own D2H finish when that is still in flight
            d2h_key = ("d2h", seq, logical)
            after = pipe.finish_of(d2h_key) or 0.0
            h2d_key = ("h2d", seq, logical)
            prefetched = pipe.finish_of(h2d_key) is not None
            if use_async:
                pipe.cancel(d2h_key)      # the h2d chains after= instead
                if not prefetched:
                    pipe.submit(pipe.H2D, h2d_key, HOST_LINK,
                                "read", self._group_bytes, after=after)
                # drain barrier before the kernel may read this page — the
                # one foreground wait; a prefetched page usually finished
                if pipe.barrier(h2d_key) == 0.0:
                    self.stats["stall_ticks_saved"] += 1
                retried = pipe.took_retries(h2d_key)
            else:
                # degraded: the foreground reads the staging copy directly,
                # so it must wait out any straggler from before the flip
                pipe.barrier(d2h_key)
                pipe.barrier(h2d_key)
        page = self.host_pages.pop((seq, logical))   # plane → (L, T, *shape)
        nbytes = sum(a.nbytes for a in page.values())
        for name in self._plane_names:
            self.dev_planes[name] = self.dev_planes[name].at[:, phys].set(
                jnp.asarray(page[name], self.dev_planes[name].dtype))
        self.block_table[seq][logical] = phys
        self.page_users[phys] = {seq: logical}
        self._heat.assign(phys)
        self._touch_page(phys)
        self._fault_mark[phys] = self._alloc_seq
        if pipe is None or (not use_async and not prefetched):
            self.clock.charge(HOST_LINK, "read", nbytes,
                              random_access=True)        # H2D fault-in
        if prefetched:
            # the scheduler's lookahead had this page's transfer in flight:
            # the demand fault becomes a (mostly) free pickup
            self.stats["prefetch_hits"] += 1
        elif retried:
            # demand fault whose H2D needed ≥1 retry: counted apart so the
            # chaos conservation law stays exact —
            # prefetch_hits + pool_faults + retried_faults == sync faults
            self.stats["retried_faults"] += 1
        else:
            self.stats["pool_faults"] += 1
        self.stats["pool_h2d_bytes"] += nbytes
        self._count_plane_bytes("pool_h2d_bytes", page)

    def _ensure_seq_resident(self, seq: int, pinned: set) -> None:
        faulted = []
        for logical, phys in enumerate(self.block_table.get(seq, [])):
            if phys < 0:
                self._fault_page(seq, logical, pinned)
                faulted.append(self.block_table[seq][logical])
            else:
                self._touch_page(phys)
                self.stats["pool_hits"] += 1
        # recency fix (ISSUE 8): the logical-order walk touches the
        # sequence's later RESIDENT pages after its early faulted ones, so
        # after a multi-page fault burst the pages that just paid an H2D sat
        # coldest in the LRU — the next allocation's first victims (thrash).
        # Re-touch the burst at the end: the whole sequence was accessed at
        # once, so its pages share one recency class and the freshly faulted
        # ones must not rank behind it.
        for phys in faulted:
            self.pool_lru.touch(phys)

    def prepare_step(self, seqs: Sequence[int], n_tokens: Sequence[int],
                     max_pages: int):
        """Multi-token step preparation (fused mixed-batch ticks): every
        batch sequence's pages are pinned — a later allocation must never
        spill a page the kernel is about to read — and each sequence gets
        pages covering its whole chunk."""
        if self._pooled and self._state_only:
            raise RuntimeError(
                "state-only descriptor has no pages; drive steps through "
                "state_views()/commit_state()")
        pinned = set(seqs)
        T = self.spec.page_tokens
        for seq, n in zip(seqs, n_tokens):
            self._check_active(seq)
            self._ensure_seq_resident(seq, pinned)
            # the kernel is about to scatter this row's tokens: if the
            # boundary page is aliased by other sequences, give this writer
            # its own copy first (copy-on-write divergence)
            self._maybe_cow_boundary(seq, pinned)
            table = self.block_table.setdefault(seq, [])
            end = self.seq_len.get(seq, 0) + max(int(n), 1)
            for _ in range(-(-end // T) - len(table)):
                self._extend_table(seq, pinned)
        tbl = np.zeros((len(seqs), max_pages), np.int32)
        lens = np.zeros(len(seqs), np.int32)
        for i, seq in enumerate(seqs):
            row = self.block_table.get(seq, [])
            if len(row) > max_pages:
                raise ValueError(
                    f"sequence {seq} spans {len(row)} pages > max_pages="
                    f"{max_pages}")
            tbl[i, :len(row)] = row
            lens[i] = self.seq_len.get(seq, 0)
        return tbl, lens

    def commit_step(self, pool_k, pool_v, seqs: Sequence[int],
                    n_tokens: Sequence[int],
                    prepared: Optional[Sequence[int]] = None) -> None:
        """Dense ``(k, v)`` special case of :meth:`commit_step_planes`."""
        return self.commit_step_planes((pool_k, pool_v), seqs, n_tokens,
                                       prepared=prepared)

    def commit_step_planes(self, planes, seqs: Sequence[int],
                           n_tokens: Sequence[int],
                           prepared: Optional[Sequence[int]] = None) -> None:
        """Commit ``n_tokens[i]`` tokens per sequence, accepting updated
        pool planes in descriptor order. With speculative decode,
        ``n_tokens[i]`` may be SMALLER than the ``prepared[i]`` count
        :meth:`prepare_step` was sized for: the rejected tail's KV was
        physically scattered (the HBM write is charged for every prepared
        slot) but never becomes visible — ``seq_len`` advances by the
        accepted count only, pages allocated solely for the tail go back
        to the free list, and stale KV inside retained pages is masked by
        the kernels (slots at or past ``lengths``) until the next
        committed tokens overwrite it in place."""
        if len(planes) != len(self._plane_names):
            raise ValueError(
                f"expected {len(self._plane_names)} pool planes "
                f"{self._plane_names}, got {len(planes)}")
        for name, arr in zip(self._plane_names, planes):
            self.dev_planes[name] = arr
        per_tok = self._token_group_bytes()
        T = self.spec.page_tokens
        for i, (seq, n) in enumerate(zip(seqs, n_tokens)):
            n = int(n)
            prep = n if prepared is None else int(prepared[i])
            pos = self.seq_len.get(seq, 0)
            self.seq_len[seq] = pos + n
            # a prepared page can be spilled mid-tick by an out-of-batch
            # allocation once the prepare pin is released — its -1 marker
            # must never enter the LRU/heat maps
            for logical in range(pos // T, -(-(pos + n) // T)):
                phys = self.block_table[seq][logical]
                if phys >= 0:
                    self._touch_page(phys)
            self.clock.charge(HBM, "write", max(prep, n) * per_tok)
            self.stats["pool_appends"] += n
            if prep > n:
                self._rewind_step_pages(seq)

    def _rewind_step_pages(self, seq: int) -> None:
        """Speculative rollback: drop trailing block-table pages past the
        committed length. Such pages are this step's fresh allocations —
        sole-user, unpinned (``_extend_table`` never hands out a shared or
        index-held page) — so they return straight to the free list; the
        guard stops at anything that doesn't match that shape.

        A trailing page may have been SPILLED between prepare and commit
        (an out-of-batch allocation can evict a prepared page once the
        batch pin is gone): its host copy holds only rejected KV. Breaking
        there — the old behavior — leaked that stale staging copy forever
        AND stranded every rolled-back page behind it (ISSUE 8). The fix
        drops the dead copy (cancelling its in-flight transfers) and keeps
        rewinding. The D2H byte counters are NOT rewound: the spill moved
        real bytes, so ``pool_d2h_bytes == pool_page_spills × page_bytes``
        stays the monotone bytes-moved invariant either way."""
        T = self.spec.page_tokens
        keep = max(-(-self.seq_len.get(seq, 0) // T), 0)
        table = self.block_table.get(seq, [])
        while len(table) > keep:
            phys = table[-1]
            if phys < 0:
                table.pop()
                logical = len(table)
                self.host_pages.pop((seq, logical), None)
                if self._pipeline is not None:
                    # rolled-back pages' transfers never need to land:
                    # reclaim their unserved channel reservations
                    self._pipeline.cancel(("d2h", seq, logical),
                                          reclaim=True)
                    self._pipeline.cancel(("h2d", seq, logical),
                                          reclaim=True)
                continue
            users = self.page_users.get(phys, {})
            if phys in self.trie_refs or users.keys() - {seq}:
                break
            table.pop()
            users.pop(seq, None)
            if not users:
                self.page_users.pop(phys, None)
                self.pool_lru.remove(phys)
                self.free_pages.append(phys)

    def alloc_prefill(self, seq: int, n_tokens: int):
        pinned = {seq}
        self._check_active(seq)
        self._ensure_seq_resident(seq, pinned)
        if n_tokens > 0:
            self._maybe_cow_boundary(seq, pinned)
        table = self.block_table.setdefault(seq, [])
        end = self.seq_len.get(seq, 0) + n_tokens
        need = -(-end // self.spec.page_tokens) - len(table)
        for _ in range(max(need, 0)):
            self._extend_table(seq, pinned)
        return np.asarray(table, np.int32)

    def commit_prefill(self, pool_k, pool_v, seq: int,
                       n_tokens: int) -> None:
        """Dense ``(k, v)`` special case of :meth:`commit_prefill_planes`."""
        return self.commit_prefill_planes((pool_k, pool_v), seq, n_tokens)

    def commit_prefill_planes(self, planes, seq: int, n_tokens: int) -> None:
        if len(planes) != len(self._plane_names):
            raise ValueError(
                f"expected {len(self._plane_names)} pool planes "
                f"{self._plane_names}, got {len(planes)}")
        for name, arr in zip(self._plane_names, planes):
            self.dev_planes[name] = arr
        self.seq_len[seq] = self.seq_len.get(seq, 0) + n_tokens
        for phys in self.block_table.get(seq, []):
            if phys >= 0:
                self._touch_page(phys)
        self.clock.charge(HBM, "write", n_tokens * self._token_group_bytes())
        self.stats["pool_appends"] += n_tokens

    def _idle_index_pages(self) -> int:
        """Index-pinned pages with no live user that allocation can ACTUALLY
        free on demand — the pressure surface must only promise headroom
        eviction can deliver (ISSUE 8). With an index registered, an idle
        pin reclaims through ``reclaim_one`` only while its trie node is
        unreferenced, so the count caps at the index's own reclaimable
        total (an idle page whose node other sequences still hold is NOT
        headroom — the old uncapped count admitted work the allocator then
        crashed on). With no index object, idle pins free directly in
        ``_alloc_page``, so the raw count stands."""
        idle = sum(1 for p in self.trie_refs if not self.page_users.get(p))
        if idle == 0 or self._share_index is None:
            return idle
        cap = getattr(self._share_index, "reclaimable_pages", None)
        return idle if cap is None else min(idle, cap())

    def can_admit_tokens(self, n_tokens: int) -> bool:
        if not self._pooled:
            return True
        if self._state_only:
            # state rows are fixed-size: admission is a row-count check
            return len(self.seq_state) < self._state_capacity
        pages_needed = -(-n_tokens // self.spec.page_tokens)
        return (pages_needed + self._reserve_pages()
                <= len(self.free_pages) + self._idle_index_pages())

    def can_place_step(self, seqs: Sequence[int],
                       n_tokens: Sequence[int]) -> bool:
        """Conservative placement check for one fused step: every page the
        batch will hold afterwards (chunk growth + faulting back any
        spilled page of a batch sequence, plus a possible boundary COW per
        row) must be coverable by free pages plus pages spillable from
        sequences OUTSIDE the batch — because ``prepare_step`` pins the
        whole batch while allocating. Shared pages (several live users)
        never spill, so they don't count; idle index-held pages reclaim
        for free, so they do."""
        if not self._pooled or self._state_only:
            return True
        T = self.spec.page_tokens
        batch = set(seqs)
        needed = 0
        for seq, n in zip(seqs, n_tokens):
            table = self.block_table.get(seq, [])
            resident = sum(1 for p in table if p >= 0)
            target = -(-(self.seq_len.get(seq, 0) + max(int(n), 1)) // T)
            needed += max(target, len(table)) - resident
            pos = self.seq_len.get(seq, 0)
            if pos % T:
                logical = pos // T
                if logical < len(table) and \
                        len(self.page_users.get(table[logical], ())) > 1:
                    needed += 1        # boundary copy-on-write page
        spillable = sum(
            1 for phys, users in self.page_users.items()
            if len(users) == 1 and next(iter(users)) not in batch)
        return needed <= (len(self.free_pages) + self._idle_index_pages()
                          + spillable)

    def _reserve_pages(self) -> int:
        """Pages the next decode step will claim: one per active sequence
        whose next token starts a fresh page."""
        if self._pooled and self._state_only:
            return 0
        T = self.spec.page_tokens
        return sum(1 for seq, n in self.seq_len.items()
                   if seq not in self._preempted
                   and n >= T * len(self.block_table.get(seq, ())))

    # ------------------------------------------------- async tier transfers
    def prefetch(self, seqs: Sequence[int],
                 n_tokens: Optional[Sequence[int]] = None) -> int:
        """Schedule background H2D fault-ins for every spilled page of next
        tick's planned batch (ISSUE 8). Timing-only: the host staging copy
        stays where it is and no page is allocated — the later demand fault
        in ``_fault_page`` materializes the page and, finding the transfer
        already in flight, pays only the residual wait (usually zero). That
        keeps allocation state bit-identical to a synchronous run, which is
        what makes ``prefetch_hits + pool_faults == sync pool_faults`` an
        exact invariant rather than an approximation."""
        if not self._pooled or self._pipeline is None \
                or self._pipeline.degraded:
            return 0
        n = 0
        for seq in seqs:
            if seq in self._preempted:
                continue
            for logical, phys in enumerate(self.block_table.get(seq, ())):
                if phys >= 0:
                    continue
                key = ("h2d", seq, logical)
                if self._pipeline.finish_of(key) is not None:
                    continue           # already in flight from a prior tick
                after = self._pipeline.finish_of(("d2h", seq, logical)) or 0.0
                self._pipeline.submit(self._pipeline.H2D, key, HOST_LINK,
                                      "read", self._group_bytes, after=after)
                n += 1
        return n

    def flush_transfers(self) -> None:
        if self._pooled and self._pipeline is not None:
            self._pipeline.flush()

    # ------------------------------------------------- faults & recovery
    def set_fault_injector(self, injector) -> None:
        """Attach the serving tier's deterministic injector (ISSUE 10).
        Transfer fail/delay decisions live in the pipeline; the spilled
        host-page loss check lives in ``_fault_page``. Placement never
        consults the injector, so transfer faults stay timing-only."""
        self._injector = injector
        if self._pipeline is not None:
            self._pipeline.injector = injector

    def abort_step(self, seqs: Sequence[int]) -> None:
        """Roll back a prepared-but-uncommitted step (exception between
        ``prepare_step`` and ``commit_step``): ``seq_len`` never advanced,
        so rewinding each row to its committed length returns exactly this
        tick's fresh allocations to the free list — a poisoned tick leaks
        no pool pages. Pages that faulted back in during prepare hold
        committed KV and stay resident."""
        if not self._pooled or self._state_only:
            return
        for seq in seqs:
            if seq in self.block_table:
                self._rewind_step_pages(seq)

    def stall_transfers(self, direction: int, seconds: float) -> None:
        if self._pooled and self._pipeline is not None:
            self._pipeline.stall_channel(direction, seconds)

    # ------------------------------------------------------- prefix sharing
    def supports_sharing(self) -> bool:
        return self._pooled and not self._state_only

    def set_share_index(self, index) -> None:
        if not self._pooled:
            raise RuntimeError("prefix sharing requires pooled mode; call "
                               "init_pool() first")
        self._share_index = index

    def page_refs(self, phys: int) -> int:
        if not self._pooled:
            return 0
        return (len(self.page_users.get(phys, ()))
                + (1 if phys in self.trie_refs else 0))

    def adopt_pages(self, seq: int, pages: Sequence[int],
                    covered_tokens: int) -> None:
        """Splice-on-admit: alias ``seq``'s block table onto shared pool
        pages covering its first ``covered_tokens`` prompt tokens. Pure
        metadata — page refcounts go up, zero KV moves, zero compute."""
        if not self._pooled:
            raise RuntimeError("adopt_pages() requires pooled mode")
        self._check_active(seq)
        if self.block_table.get(seq) or self.seq_len.get(seq):
            raise RuntimeError(
                f"sequence {seq} already holds pages; prefix splice is "
                f"admission-only")
        if len(pages) != -(-covered_tokens // self.spec.page_tokens):
            raise ValueError(
                f"{len(pages)} pages cannot cover {covered_tokens} tokens "
                f"at {self.spec.page_tokens} tokens/page")
        table = self.block_table[seq] = []
        for logical, phys in enumerate(pages):
            users = self.page_users.setdefault(phys, {})
            if len(users) == 1:
                self.stats["shared_pages"] += 1   # gained a 2nd live user
            users[seq] = logical
            table.append(phys)
            self._touch_page(phys)
        self.seq_len[seq] = covered_tokens
        self.stats["prefix_hits"] += 1
        self.stats["prefix_tokens_reused"] += covered_tokens

    def pin_page(self, phys: int) -> None:
        if phys in self.trie_refs:
            return
        if self.page_users.get(phys):
            self.stats["shared_pages"] += 1       # index + live user(s)
        self.trie_refs.add(phys)

    def unpin_page(self, phys: int) -> None:
        self.trie_refs.discard(phys)
        if not self.page_users.get(phys):
            # the index was the last referent: free the page
            self.page_users.pop(phys, None)
            if phys in self.pool_lru:
                self.pool_lru.remove(phys)
                self.free_pages.append(phys)

    def _maybe_cow_boundary(self, seq: int, pinned: set) -> None:
        """Copy-on-write before a write lands mid-page: the next token slot
        of ``seq`` falls inside an existing page — if that page is aliased
        by OTHER live sequences, the writer gets a private copy first and
        readers keep the original. A page whose only other referent is the
        prefix index needs no copy: splicers trust only the first
        ``covered`` slots (the kernel masks beyond each row's length), and
        those slots are never rewritten with different values."""
        T = self.spec.page_tokens
        pos = self.seq_len.get(seq, 0)
        if pos % T == 0:
            return                     # next write starts a fresh page
        logical = pos // T
        table = self.block_table.get(seq, ())
        if logical >= len(table):
            return
        phys = table[logical]
        if phys < 0 or len(self.page_users.get(phys, ())) <= 1:
            return
        self._cow_page(seq, logical, pinned)

    def _cow_page(self, seq: int, logical: int, pinned: set) -> None:
        """Duplicate ``seq``'s view of a shared page into a fresh physical
        page (one on-device page copy) and retarget its block table; every
        other referent — sequences and the prefix index — keeps the
        original."""
        # lazy import: repro.serving.batching owns the device-pool helpers
        # and importing it at module scope would cycle through the serving
        # package
        from repro.serving.batching import copy_pool_page_planes
        phys = self.block_table[seq][logical]
        new = self._alloc_page(set(pinned) | {seq})
        copied = copy_pool_page_planes(
            tuple(self.dev_planes[n] for n in self._plane_names), phys, new)
        for name, arr in zip(self._plane_names, copied):
            self.dev_planes[name] = arr
        self.page_users[phys].pop(seq, None)
        self.page_users[new] = {seq: logical}
        self.block_table[seq][logical] = new
        self._heat.assign(new)
        self._touch_page(new)
        self.clock.charge(HBM, "read", self._group_bytes)
        self.clock.charge(HBM, "write", self._group_bytes)
        self.stats["cow_copies"] += 1
        if self._share_index is not None:
            self._share_index.on_cow(seq, phys)

    # ------------------------------------------------------ per-seq state rows
    # SSM configs pool ZERO paged planes: their cache is a fixed-size state
    # row per sequence (descriptor seq_planes) that rides alongside the
    # block tables — committed with the row each step, spilled/preempted/
    # restored whole, and rolled back by committing an earlier slot's state.
    def state_views(self, seqs: Sequence[int]):
        """Batched state rows for one step: one ``(L, B, *shape)`` array
        per seq plane in descriptor order. Sequences without committed
        state yet (fresh admissions) read zero-initialized rows."""
        import jax.numpy as jnp
        if not self._pooled or not self.desc.has_state:
            raise RuntimeError("state_views() requires a pooled engine with "
                               "a state-bearing descriptor")
        out = []
        for p in self.desc.seq_planes:
            zero = None
            rows = []
            for seq in seqs:
                arr = self.seq_state.get(seq, {}).get(p.name)
                if arr is None:
                    if zero is None:
                        zero = jnp.zeros(
                            (self.spec.num_layers,) + tuple(p.shape),
                            p.np_dtype)
                    arr = zero
                rows.append(arr)
            out.append(jnp.stack(rows, axis=1))
        return tuple(out)

    def commit_state(self, seqs: Sequence[int], n_tokens: Sequence[int],
                     states) -> None:
        """Commit one step's updated state rows. ``states``: one
        ``(L, B, *shape)`` per seq plane (descriptor order); row ``i``
        becomes ``seqs[i]``'s new state and ``seq_len`` advances by
        ``n_tokens[i]``. Rows with ``n_tokens[i] == 0`` (batch padding,
        fully-rejected speculative rows) commit NOTHING — their stored
        state is untouched, which is the state-row form of the paged
        rewind rule."""
        if not self._pooled or not self.desc.has_state:
            raise RuntimeError("commit_state() requires a pooled engine "
                               "with a state-bearing descriptor")
        live = 0
        for i, (seq, n) in enumerate(zip(seqs, n_tokens)):
            n = int(n)
            if n <= 0:
                continue
            self._check_active(seq)
            live += 1
            row = self.seq_state.setdefault(seq, {})
            for p, arr in zip(self.desc.seq_planes, states):
                row[p.name] = arr[:, i]
            self.seq_len[seq] = self.seq_len.get(seq, 0) + n
            self.stats["pool_appends"] += n
        self.clock.charge(HBM, "write", live * self.desc.seq_state_bytes)

    def _spill_state_planes(self, seq: int) -> dict:
        """Preemption blobs for a state-only sequence: the device state
        rows come down over the link (D2H), one array per seq plane."""
        blobs = {}
        for p in self.desc.seq_planes:
            arr = self.seq_state.get(seq, {}).get(p.name)
            if arr is None:
                arr = np.zeros((self.spec.num_layers,) + tuple(p.shape),
                               p.np_dtype)
            blobs[p.name] = np.asarray(arr)
        nbytes = sum(a.nbytes for a in blobs.values())
        self.clock.charge(HOST_LINK, "write", nbytes, random_access=False)
        self.stats["pool_d2h_bytes"] += nbytes
        self._count_plane_bytes("pool_d2h_bytes", blobs)
        return blobs

    def _restore_state_planes(self, seq: int, length: int,
                              blobs: dict) -> None:
        import jax.numpy as jnp
        self.seq_state[seq] = {n: jnp.asarray(a) for n, a in blobs.items()}
        nbytes = sum(a.nbytes for a in blobs.values())
        self.clock.charge(HOST_LINK, "read", nbytes, random_access=False)
        self.clock.charge(HBM, "write", nbytes)
        self.stats["pool_h2d_bytes"] += nbytes
        self._count_plane_bytes("pool_h2d_bytes", blobs)
        self.seq_len[seq] = length

    # --------------------------------------------- pooled preempt / restore
    def preempt(self, seq: int) -> None:
        """Pooled preemption spills PLANE blobs (one token-exact array per
        paged plane, or the state rows) rather than the host engines'
        dense ``(L, 2, T, K, D)`` blob — the layout leaves the pool the
        same way it lives in it."""
        if not self._pooled:
            return super().preempt(seq)
        self._check_active(seq)
        length = self.seq_len.get(seq, 0)
        blobs = (self._spill_state_planes(seq) if self._state_only
                 else self._spill_pooled_planes(seq))
        nbytes = sum(a.nbytes for a in blobs.values())
        # sequential drain of the whole sequence out of the host tier and
        # onto the disk tier (one streamed copy, no random faults)
        self.clock.charge(HOST_LINK, "read", nbytes, random_access=False)
        self.clock.charge(SSD, "write", nbytes, random_access=False)
        self._drop_seq(seq)
        self.seq_len.pop(seq, None)
        self._preempted[seq] = (length, blobs)
        self.stats["preempts"] += 1
        self.stats["preempt_out_bytes"] += nbytes

    def restore(self, seq: int) -> None:
        if not self._pooled:
            return super().restore(seq)
        item = self._preempted.pop(seq, None)
        if item is None:
            raise RuntimeError(f"sequence {seq} is not preempted")
        length, blobs = item
        nbytes = sum(a.nbytes for a in blobs.values())
        self.clock.charge(SSD, "read", nbytes, random_access=False)
        self.stats["restores"] += 1
        self.stats["restore_in_bytes"] += nbytes
        if self._state_only:
            self._restore_state_planes(seq, length, blobs)
        else:
            self._restore_pooled_planes(seq, length, blobs)

    def _restore_pooled_planes(self, seq: int, length: int,
                               blobs: dict) -> None:
        """Scatter a preempted sequence's plane blobs into fresh pool
        pages: disk → host (charged by :meth:`restore`) → device (PCIe
        upload + HBM write). Pages come from the same allocator as any
        append, so a tight pool may spill other sequences to make room."""
        import jax.numpy as jnp
        spec = self.spec
        pinned = {seq}
        table = self.block_table.setdefault(seq, [])
        npages = -(-length // spec.page_tokens)
        for _ in range(npages - len(table)):
            self._extend_table(seq, pinned)
        for logical in range(npages):
            lo = logical * spec.page_tokens
            hi = min(lo + spec.page_tokens, length)
            phys = table[logical]
            for name in self._plane_names:
                plane = self.dev_planes[name]
                chunk = jnp.asarray(blobs[name][:, lo:hi], plane.dtype)
                self.dev_planes[name] = \
                    plane.at[:, phys, :hi - lo].set(chunk)
            self._touch_page(phys)
        nbytes = sum(a.nbytes for a in blobs.values())
        self.clock.charge(HOST_LINK, "read", nbytes, random_access=False)
        self.clock.charge(HBM, "write", nbytes)
        self.stats["pool_h2d_bytes"] += nbytes
        self._count_plane_bytes("pool_h2d_bytes", blobs)
        self.stats["pool_appends"] += length
        self.seq_len[seq] = length

    # pooled data paths ------------------------------------------------------
    def _append_tokens_pooled(self, seq: int, toks: list[np.ndarray]) -> None:
        """Host-facing append in pooled mode (benchmarks and the sequential
        mirror): scatter into the device pool. Decode-shaped appends model
        device-born tokens (HBM write only). Dense ``(k, v)`` layouts only
        — other families' hosts-side callers have no dense token format."""
        import jax.numpy as jnp
        if self.desc.kernel != "dense":
            raise NotImplementedError(
                f"host-facing appends are dense-only; {self.desc.family!r} "
                f"pools are fed on device via commit_step_planes/"
                f"commit_prefill_planes")
        spec = self.spec
        pinned = {seq}
        self._ensure_seq_resident(seq, pinned)
        if toks:
            self._maybe_cow_boundary(seq, pinned)
        table = self.block_table.setdefault(seq, [])
        start = self.seq_len.get(seq, 0)
        end = start + len(toks)
        for _ in range(-(-end // spec.page_tokens) - len(table)):
            self._extend_table(seq, pinned)
        arr = np.stack(toks)                      # (n, L, 2, K, D)
        for logical in range(start // spec.page_tokens,
                             -(-end // spec.page_tokens)):
            lo = max(start, logical * spec.page_tokens)
            hi = min(end, (logical + 1) * spec.page_tokens)
            sl = slice(lo - logical * spec.page_tokens,
                       hi - logical * spec.page_tokens)
            chunk = arr[lo - start:hi - start]    # (m, L, 2, K, D)
            phys = table[logical]
            self.dev_planes["k"] = self.dev_planes["k"].at[:, phys, sl].set(
                jnp.asarray(chunk[:, :, 0].transpose(1, 0, 2, 3),
                            self.pool_dtype))
            self.dev_planes["v"] = self.dev_planes["v"].at[:, phys, sl].set(
                jnp.asarray(chunk[:, :, 1].transpose(1, 0, 2, 3),
                            self.pool_dtype))
            self._touch_page(phys)
        nbytes = len(toks) * self._token_group_bytes()
        self.clock.charge(HBM, "write", nbytes)
        self.stats["pool_appends"] += len(toks)
        self.seq_len[seq] = end

    def _read_pooled(self, seq: int, layer: int) -> np.ndarray:
        spec = self.spec
        if self.desc.kernel != "dense":
            raise NotImplementedError(
                f"host-facing reads are dense-only; {self.desc.family!r} "
                f"pools are consumed on device through pool_views()")
        self._ensure_seq_resident(seq, {seq})
        T = self.seq_len.get(seq, 0)
        out = np.zeros((2, T, spec.kv_heads, spec.head_dim), spec.dtype)
        dev_k, dev_v = self.dev_planes["k"], self.dev_planes["v"]
        for logical, phys in enumerate(self.block_table.get(seq, [])):
            lo = logical * spec.page_tokens
            hi = min(lo + spec.page_tokens, T)
            if lo >= T:
                break
            out[0, lo:hi] = np.asarray(
                dev_k[layer, phys, :hi - lo]).astype(spec.dtype)
            out[1, lo:hi] = np.asarray(
                dev_v[layer, phys, :hi - lo]).astype(spec.dtype)
            self._touch_page(phys)
            self.clock.charge(HBM, "read", (hi - lo) * spec.token_bytes)
        return out

    def _spill_pooled_planes(self, seq: int) -> dict:
        """Whole-sequence preemption blobs — one token-exact
        ``(L, T, *shape)`` array per paged plane — gathered page by page:
        resident pages pay a D2H transfer each, already-spilled pages are
        host-side copies (no device traffic)."""
        spec = self.spec
        T = self.seq_len.get(seq, 0)
        blobs = {p.name: np.zeros((spec.num_layers, T) + tuple(p.shape),
                                  p.np_dtype)
                 for p in self.desc.paged_planes}
        for logical, phys in enumerate(self.block_table.get(seq, [])):
            lo = logical * spec.page_tokens
            hi = min(lo + spec.page_tokens, T)
            if lo >= T:
                break
            if phys < 0:
                if self._pipeline is not None:
                    # coherence barrier: the staging copy may still be in
                    # flight to the host — never read an in-flight page
                    self._pipeline.barrier(("d2h", seq, logical))
                page = self.host_pages[(seq, logical)]
            else:
                page = self._page_planes_np(phys)
                nbytes = sum(a.nbytes for a in page.values())
                self.clock.charge(HOST_LINK, "write", nbytes,
                                  random_access=True)      # D2H page out
                self.stats["pool_d2h_bytes"] += nbytes
                self.stats["pool_page_spills"] += 1
                self._count_plane_bytes("pool_d2h_bytes", page)
            for name, arr in page.items():
                blobs[name][:, lo:hi] = arr[:, :hi - lo]
        return blobs

    def _drop_seq_pooled(self, seq: int) -> None:
        """Release ``seq``'s pages (and any state rows): shared pages only
        lose this sequence's refcount; a page returns to the free list
        when its last live user leaves AND the prefix index does not pin
        it."""
        self.seq_state.pop(seq, None)
        for logical, phys in enumerate(self.block_table.pop(seq, [])):
            if phys >= 0:
                users = self.page_users.get(phys, {})
                users.pop(seq, None)
                if not users:
                    self.page_users.pop(phys, None)
                    if phys not in self.trie_refs:
                        self.pool_lru.remove(phys)
                        self.free_pages.append(phys)
            else:
                self.host_pages.pop((seq, logical), None)
        if self._pipeline is not None:
            # a later sequence may reuse this id: its (dir, seq, logical)
            # keys must not inherit this sequence's in-flight transfers
            self._pipeline.cancel_seq(seq)
        if self._share_index is not None:
            self._share_index.on_seq_dropped(seq)

    def _ensure_resident(self, layer: int, phys: int) -> None:
        key = (layer, phys)
        if key in self.hbm_lru:
            self.stats["hbm_hits"] += 1
            self.hbm_lru.touch(key)
            return
        self.stats["hbm_misses"] += 1
        if len(self.hbm_lru) >= self.hbm_capacity:
            self.hbm_lru.pop_lru()                   # clean: host copy is truth
        # DMA whole page up — the paper's miss-copy cost
        self.clock.charge(HOST_LINK, "read", self.spec.page_bytes,
                          random_access=True)
        self.stats["dma_up_bytes"] += self.spec.page_bytes
        self.hbm_lru.touch(key)

    def _touch_resident(self, layer: int, phys: int) -> None:
        """Mark the page being appended to as HBM-resident. The token just
        came out of the device, so the page is in the working set by
        construction — no DMA and no hit/miss accounting (those are
        read-path stats)."""
        if len(self.hbm_lru) >= self.hbm_capacity and \
                (layer, phys) not in self.hbm_lru:
            self.hbm_lru.pop_lru()
        self.hbm_lru.touch((layer, phys))

    def _append_tokens(self, seq: int, toks: list[np.ndarray]) -> None:
        if self._pooled:
            return self._append_tokens_pooled(seq, toks)
        spec = self.spec
        for kv_token in toks:
            pos = self.seq_len.get(seq, 0)
            logical = pos // spec.page_tokens
            slot = pos % spec.page_tokens
            table = self.block_table.setdefault(seq, [])
            if logical >= len(table):
                table.append(self.next_phys)
                self.next_phys += 1
                for layer in range(spec.num_layers):
                    self.pool[(layer, table[logical])] = spec.empty_page()
            phys = table[logical]
            for layer in range(spec.num_layers):
                # redo-buffer write then page write: the paging design's 2×
                self.clock.charge(HOST_LINK, "write", spec.token_bytes,
                                  random_access=False)       # redo append
                self.stats["redo_bytes"] += spec.token_bytes
                self.clock.charge(HOST_LINK, "write", spec.token_bytes,
                                  random_access=True)        # into the page
                self.stats["host_writes"] += 1
                self.pool[(layer, phys)][:, slot] = kv_token[layer]
                self._touch_resident(layer, phys)
            self.seq_len[seq] = pos + 1

    def _read(self, seq: int, layer: int) -> np.ndarray:
        """Materialize (2, T, kv_heads, head_dim) for attention; pages are
        DMA'd to HBM on miss (block-table indirection)."""
        if self._pooled:
            return self._read_pooled(seq, layer)
        spec = self.spec
        T = self.seq_len.get(seq, 0)
        out = np.zeros((2, T, spec.kv_heads, spec.head_dim), spec.dtype)
        for logical, phys in enumerate(self.block_table.get(seq, [])):
            self._ensure_resident(layer, phys)
            lo = logical * spec.page_tokens
            hi = min(lo + spec.page_tokens, T)
            if lo >= T:
                break
            page = self.pool[(layer, phys)]
            out[:, lo:hi] = page[:, :hi - lo]
            self.clock.charge(HBM, "read", (hi - lo) * spec.token_bytes)
        return out

    def _spill(self, seq: int) -> np.ndarray:
        if self._pooled:
            raise RuntimeError(
                "pooled preemption goes through plane blobs, not the dense "
                "host spill hook")
        spec = self.spec
        T = self.seq_len.get(seq, 0)
        blob = np.zeros((spec.num_layers, 2, T, spec.kv_heads,
                         spec.head_dim), spec.dtype)
        for logical, phys in enumerate(self.block_table.get(seq, [])):
            lo = logical * spec.page_tokens
            hi = min(lo + spec.page_tokens, T)
            if lo >= T:
                break
            for layer in range(spec.num_layers):
                blob[layer, :, lo:hi] = self.pool[(layer, phys)][:, :hi - lo]
        return blob

    def _drop_seq(self, seq: int) -> None:
        if self._pooled:
            return self._drop_seq_pooled(seq)
        for phys in self.block_table.pop(seq, []):
            for layer in range(self.spec.num_layers):
                self.pool.pop((layer, phys), None)
                self.hbm_lru.remove((layer, phys))

    # -------------------------------------------------------------- pressure
    def hbm_used_bytes(self) -> int:
        if self._pooled:
            if self._state_only:
                return len(self.seq_state) * self.desc.seq_state_bytes
            return ((self.pool_pages - len(self.free_pages))
                    * self._group_bytes)
        return len(self.hbm_lru) * self.spec.page_bytes

    def hbm_limit_bytes(self) -> Optional[int]:
        if self._pooled:
            if self._state_only:
                return self._state_capacity * self.desc.seq_state_bytes
            return self.pool_pages * self._group_bytes
        return self.hbm_capacity * self.spec.page_bytes

    def pressure(self) -> float:
        if not self._pooled:
            return super().pressure()
        if self._state_only:
            return min(len(self.seq_state) / self._state_capacity, 1.0)
        # count the pages the NEXT decode step will claim, so the scheduler
        # preempts one tick before allocation would have to spill pages of
        # the running batch itself (page-granular early warning); pages held
        # only by the prefix index are reclaimable on demand, so they count
        # as headroom rather than load
        used = (self.pool_pages - len(self.free_pages)
                - self._idle_index_pages() + self._reserve_pages())
        return min(used / self.pool_pages, 1.0)

    def resident_bytes(self, seq: int) -> int:
        if self._pooled:
            if self._state_only:
                return (self.desc.seq_state_bytes
                        if seq in self.seq_state else 0)
            n = sum(1 for phys in self.block_table.get(seq, ()) if phys >= 0)
            return n * self._group_bytes
        n = sum(1 for phys in self.block_table.get(seq, ())
                for layer in range(self.spec.num_layers)
                if (layer, phys) in self.hbm_lru)
        return n * self.spec.page_bytes

    def victim_hint(self, candidates: Iterable[int]) -> Optional[int]:
        """Pooled mode answers at page granularity: preempt the candidate
        whose eviction actually FREES the most device pool pages — a page
        this sequence shares with other rows (or that the prefix index
        pins) stays resident after the preempt, so only sole-user unpinned
        pages count. Ties rank by the hot/cold model (ISSUE 8): prefer the
        candidate whose freeable pages carry the least re-reference mass
        (``PageHeat.hotness`` summed — evicting them forfeits the fewest
        expected future hits), then by LRU coldness. Host mode keeps the
        LRU fallback."""
        if not self._pooled or self._state_only:
            return None
        cands = list(candidates)
        if not cands:
            return None
        order = {phys: i for i, phys in enumerate(self.pool_lru.lru_order())}

        def key(seq):
            pages = [p for p in self.block_table.get(seq, ()) if p >= 0]
            freeable = [p for p in pages
                        if len(self.page_users.get(p, ())) == 1
                        and p not in self.trie_refs]
            heat = sum(self._heat.hotness(p) for p in freeable)
            coldest = min((order.get(p, len(order)) for p in pages),
                          default=len(order))
            return (-len(freeable), heat, coldest)
        return min(cands, key=key)


class _DrainingKV(_TieredKV):
    """Shared log/drain machinery for the log-structured designs.

    Appends go to a sequential host log (1× write) whose entries drain into
    compacted host pages through :class:`ShardedDrainer` — per-shard pending
    queues (``hash(seq) → shard``), each an independent FIFO server, so
    backlog on one shard never delays another. A per-sequence HBM hot
    window serves recent tokens; cold reads come from the compacted pages,
    patched from undrained log entries (the ``log_patch`` kernel's layout).
    """

    def __init__(self, spec: KVSpec, clock: SimClock, *,
                 hot_window_tokens: int, drain_batch: int, drain_shards: int,
                 hbm_budget_bytes: Optional[int] = None):
        super().__init__(spec, clock)
        self.hot_window = hot_window_tokens
        # the hot windows are the engine's HBM use: bound their TOTAL across
        # sequences to the budget (None = unbounded, the legacy behavior of
        # the direct constructors)
        per_token = spec.token_bytes * spec.num_layers
        self._hot_budget_tokens = (None if hbm_budget_bytes is None
                                   else max(hbm_budget_bytes // per_token, 1))
        self._hot_total = 0
        self._batch_depth = 0      # >0 inside append_many: advance once
        self.drain_batch = drain_batch
        self.drainer = ShardedDrainer(drain_shards)
        # per-shard pending log entries: (seq, pos, kv_token, finish)
        self.shard_log: list[deque] = [deque() for _ in range(drain_shards)]
        self._seq_pending: dict[int, int] = {}   # seq → undrained entries
        # compacted host pages, indexed per sequence so preempting one
        # sequence never scans the others: seq → (layer, logical) → page
        self.pages: dict[int, dict[tuple, np.ndarray]] = {}
        # per-sequence HBM hot window (most recent tokens, all layers)
        self.hot: dict[int, deque] = {}
        self.stats.update({"log_appends": 0, "patches": 0, "hot_hits": 0,
                           "host_reads": 0, "host_writes": 0, "drained": 0,
                           "stall_time": 0.0})

    def pending_for(self, seq: int) -> int:
        """Undrained log entries for ``seq`` (0 after a force-drain)."""
        return self._seq_pending.get(seq, 0)

    # ---------------------------------------------------------------- drain
    def _drain_service(self) -> float:
        b = self.spec.token_bytes * self.spec.num_layers
        return HOST_LINK.write_latency / self.drain_batch + b / HOST_LINK.write_bw

    def _apply(self, seq: int, pos: int, kv_token: np.ndarray) -> None:
        spec = self.spec
        logical, slot = divmod(pos, spec.page_tokens)
        seq_pages = self.pages.setdefault(seq, {})
        for layer in range(spec.num_layers):
            page = seq_pages.get((layer, logical))
            if page is None:
                page = spec.empty_page()
                seq_pages[(layer, logical)] = page
            page[:, slot] = kv_token[layer]

    def _advance(self, now: float) -> None:
        """Functionally apply every entry whose drain finished by ``now``."""
        for pending in self.shard_log:
            while pending and pending[0][3] <= now:
                seq, pos, kv_token, _ = pending.popleft()
                self._apply(seq, pos, kv_token)
                self._seq_pending[seq] -= 1
                if not self._seq_pending[seq]:
                    del self._seq_pending[seq]
                self.stats["drained"] += 1

    def _force_drain_seq(self, seq: int) -> None:
        """Stall until every pending entry of ``seq`` has drained. FIFO
        shard order means waiting for the sequence's newest entry drains
        everything it appended earlier too; other shards keep their own
        schedule."""
        if not self._seq_pending.get(seq, 0):
            return
        pending = self.shard_log[self.drainer.shard_of(seq)]
        finish = max(e[3] for e in pending if e[0] == seq)
        stall = max(0.0, finish - self.clock.now)
        if stall:
            self.stats["stall_time"] += stall
        self.clock.wait_until(finish)
        self._advance(self.clock.now)

    # --------------------------------------------------------------- append
    def _hot_push(self, seq: int, pos: int, kv_token: np.ndarray) -> None:
        hot = self.hot.setdefault(seq, deque())
        hot.append((pos, kv_token.copy()))
        self._hot_total += 1
        if len(hot) > self.hot_window:       # per-sequence recency window
            hot.popleft()
            self._hot_total -= 1
        while (self._hot_budget_tokens is not None
               and self._hot_total > self._hot_budget_tokens):
            # global HBM budget: shrink the largest window first (evicted
            # tokens stay readable through the cold pages/patch path)
            victim = max(self.hot.values(), key=len)
            victim.popleft()
            self._hot_total -= 1

    def _log_takes_page(self, seq: int, logical: int) -> None:
        """Hook: the log (re)gains responsibility for a page (kvhybrid's
        ownership bookkeeping)."""

    def _log_owns(self, seq: int, logical: int) -> bool:
        """Hook: may the log patch this page on read? Always true for the
        pure log design; kvhybrid answers false for page-side-owned pages
        (reads trust the page side once ownership transferred)."""
        return True

    def _append_log(self, seq: int, toks: list[np.ndarray]) -> None:
        spec = self.spec
        shard = self.drainer.shard_of(seq)
        pending = self.shard_log[shard]
        for kv_token in toks:
            pos = self.seq_len.get(seq, 0)
            nbytes = spec.token_bytes * spec.num_layers
            # one sequential log write — the logging design's 1× write
            self.clock.charge(HOST_LINK, "write", nbytes, random_access=False)
            self.stats["host_writes"] += 1
            finish = self.drainer.push(shard, self.clock.now,
                                       self._drain_service())
            pending.append((seq, pos, kv_token.copy(), finish))
            self._seq_pending[seq] = self._seq_pending.get(seq, 0) + 1
            self.stats["log_appends"] += 1
            self._log_takes_page(seq, pos // spec.page_tokens)
            self._hot_push(seq, pos, kv_token)
            self.seq_len[seq] = pos + 1

    def append_many(self, items: Sequence[tuple[int, np.ndarray]]) -> None:
        """Batched multi-sequence append with ONE drainer advance for the
        whole batch (per-append advances are suppressed while inside)."""
        self._batch_depth += 1
        try:
            for seq, kv_tokens in items:
                self.append(seq, kv_tokens)
        finally:
            self._batch_depth -= 1
        self._advance(self.clock.now)

    # ----------------------------------------------------------------- read
    def _observe_read(self, seq: int, hot_tokens: int, cold_tokens: int,
                      latency_s: float) -> None:
        """Hook: reuse + gather-latency feedback for the adaptive router
        (kvhybrid)."""

    def _read(self, seq: int, layer: int) -> np.ndarray:
        """(2, T, kv_heads, head_dim): hot window from HBM; cold history from
        compacted pages, patched from the log where the drainer hasn't
        caught up."""
        spec = self.spec
        t_read0 = self.clock.now
        self._advance(self.clock.now)
        T = self.seq_len.get(seq, 0)
        out = np.zeros((2, T, spec.kv_heads, spec.head_dim), spec.dtype)
        hot = self.hot.get(seq, ())
        hot_positions = set()
        for pos, kv_token in hot:
            out[:, pos] = kv_token[layer]
            hot_positions.add(pos)
        if hot_positions:
            self.stats["hot_hits"] += len(hot_positions)
            self.clock.charge(
                HBM, "read", len(hot_positions) * spec.token_bytes)
        cold_T = min(T, min(hot_positions) if hot_positions else T)
        npages = -(-cold_T // spec.page_tokens) if cold_T else 0
        seq_pages = self.pages.get(seq, {})
        for logical in range(npages):
            lo = logical * spec.page_tokens
            hi = min(lo + spec.page_tokens, cold_T)
            page = seq_pages.get((layer, logical))
            if page is not None:
                # only existing compacted pages cost host traffic; a still-
                # undrained page's tokens are charged by the patch loop below
                out[:, lo:hi] = page[:, :hi - lo]
                self.clock.charge(HOST_LINK, "read",
                                  (hi - lo) * spec.token_bytes,
                                  random_access=False)
                self.stats["host_reads"] += 1
        # patch undrained log entries overlapping the cold range — the
        # sequence's entries live only in its own shard (hash(seq) → shard),
        # so other shards' backlogs are never scanned
        pending = self.shard_log[self.drainer.shard_of(seq)]
        for seq_i, pos, kv_token, _ in pending:
            if (seq_i == seq and pos < cold_T and pos not in hot_positions
                    and self._log_owns(seq, pos // spec.page_tokens)):
                out[:, pos] = kv_token[layer]
                self.clock.charge(HOST_LINK, "read", spec.token_bytes,
                                  random_access=True)
                self.stats["patches"] += 1
        self._observe_read(seq, len(hot_positions), max(cold_T, 0),
                           self.clock.now - t_read0)
        return out

    def _spill(self, seq: int) -> np.ndarray:
        spec = self.spec
        T = self.seq_len.get(seq, 0)
        blob = np.zeros((spec.num_layers, 2, T, spec.kv_heads,
                         spec.head_dim), spec.dtype)
        # compacted pages first, then undrained log entries on top (FIFO) —
        # together they hold every appended token; the hot window is only a
        # cache of the same data
        for (layer, logical), page in self.pages.get(seq, {}).items():
            lo = logical * spec.page_tokens
            hi = min(lo + spec.page_tokens, T)
            if lo < T:
                blob[layer, :, lo:hi] = page[:, :hi - lo]
        for seq_i, pos, kv_token, _ in self.shard_log[
                self.drainer.shard_of(seq)]:
            if seq_i == seq:
                blob[:, :, pos] = kv_token
        return blob

    def _drop_seq(self, seq: int) -> None:
        self._hot_total -= len(self.hot.pop(seq, ()))
        self.pages.pop(seq, None)
        if self._seq_pending.pop(seq, None):
            shard = self.drainer.shard_of(seq)
            self.shard_log[shard] = deque(
                e for e in self.shard_log[shard] if e[0] != seq)

    # -------------------------------------------------------------- pressure
    def hbm_used_bytes(self) -> int:
        return self._hot_total * self.spec.token_bytes * self.spec.num_layers

    def hbm_limit_bytes(self) -> Optional[int]:
        if self._hot_budget_tokens is None:
            return None
        return (self._hot_budget_tokens * self.spec.token_bytes
                * self.spec.num_layers)

    def resident_bytes(self, seq: int) -> int:
        return (len(self.hot.get(seq, ())) * self.spec.token_bytes
                * self.spec.num_layers)


@register_kv_engine("log")
class LogKVCache(_DrainingKV):
    """NVLog design: sequential host log + HBM hot window + drain/compact."""

    def __init__(self, spec: KVSpec, clock: SimClock, *,
                 hot_window_tokens: int = 256, drain_batch: int = 32,
                 drain_shards: int = 1,
                 hbm_budget_bytes: Optional[int] = None):
        super().__init__(spec, clock, hot_window_tokens=hot_window_tokens,
                         drain_batch=drain_batch, drain_shards=drain_shards,
                         hbm_budget_bytes=hbm_budget_bytes)

    @classmethod
    def from_spec(cls, spec: EngineSpec, kvspec: KVSpec,
                  clock: SimClock) -> "LogKVCache":
        return cls(kvspec, clock, hot_window_tokens=spec.kv_hot_window,
                   drain_batch=spec.drain_batch,
                   drain_shards=spec.drain_shards,
                   hbm_budget_bytes=spec.kv_hbm_bytes)

    def _append_tokens(self, seq: int, toks: list[np.ndarray]) -> None:
        self._append_log(seq, toks)
        if not self._batch_depth:
            self._advance(self.clock.now)


class AdaptiveRouter:
    """Online log-vs-pages routing policy for :class:`HybridKVCache`.

    Keeps a log2 histogram of observed append sizes plus hot/cold read
    counters and a gather-latency EMA, and re-learns the byte threshold
    every ``update_every`` appends (appends below the threshold route to
    the log hot-window path, the rest to pages):

    * **bimodal** sizes (decode tokens vs prefill bursts): the threshold
      sits in the widest histogram valley, nudged toward the log side when
      reads are cold-heavy (pages gather long histories cheaper) and toward
      the page side when the hot window serves most reads;
    * **unimodal small** (< page granularity): everything logs — the
      threshold parks at 4× the mode, capped at one page (the paper's
      conclusion: logging wins writes below page granularity);
    * **unimodal large** (≥ one page): everything pages — full-page appends
      pay no redo write and gathers skip patching.

    **Latency feedback:** counts say where reads land; ``latency_s`` says
    what they cost. The router keeps an EMA of observed per-token gather
    latency and compares it to ``page_per_token_s`` — the modeled cost of
    serving the same token from a compacted page. When gathers run hot
    (patch-dominated reads behind a backlogged drainer), the bias shifts
    toward pages regardless of what the counts alone would say; when
    gathers are cheap the log keeps its sub-page wins.

    Per-sequence hot/cold counters (``seq_reuse``) feed
    :meth:`HybridKVCache.victim_hint`: under HBM pressure the scheduler
    preempts the sequence whose reads reuse the hot window least.
    """

    #: observed-vs-modeled gather cost ratio above which gathers count as
    #: slow (bias toward pages) / below which as cheap (keep the log)
    SLOW_GATHER_RATIO = 2.0
    FAST_GATHER_RATIO = 1.2

    def __init__(self, threshold_bytes: int, page_bytes: int, *,
                 update_every: int = 16,
                 page_per_token_s: Optional[float] = None):
        self.threshold = max(int(threshold_bytes), 1)
        self.page_bytes = page_bytes
        self.update_every = update_every
        self.page_per_token_s = page_per_token_s
        self.hist: dict[int, int] = {}    # log2 bucket → append count
        self.hot_reads = 0
        self.cold_reads = 0
        self.gather_lat_s: Optional[float] = None   # per-token EMA
        self.seq_reuse: dict[int, list[int]] = {}   # seq → [hot, cold]
        self._n = 0

    def observe_read(self, seq: int, hot_tokens: int, cold_tokens: int,
                     latency_s: float = 0.0) -> None:
        self.hot_reads += hot_tokens
        self.cold_reads += cold_tokens
        reuse = self.seq_reuse.setdefault(seq, [0, 0])
        reuse[0] += hot_tokens
        reuse[1] += cold_tokens
        tokens = hot_tokens + cold_tokens
        if tokens and latency_s > 0.0:
            per_tok = latency_s / tokens
            self.gather_lat_s = (per_tok if self.gather_lat_s is None
                                 else 0.8 * self.gather_lat_s + 0.2 * per_tok)

    def reuse_score(self, seq: int) -> Optional[float]:
        """Hot-window share of this sequence's observed reads (None = never
        read). Low score = cold sequence = cheap preemption victim."""
        reuse = self.seq_reuse.get(seq)
        if reuse is None or (reuse[0] + reuse[1]) == 0:
            return None
        return reuse[0] / (reuse[0] + reuse[1])

    def forget_seq(self, seq: int) -> None:
        """Drop per-sequence reuse state (finished request)."""
        self.seq_reuse.pop(seq, None)

    def _latency_bias(self) -> float:
        """Extra threshold bias from *observed* gather latency: slow gathers
        (≫ the modeled page-read cost) push appends toward pages, cheap
        ones keep the log attractive."""
        if self.gather_lat_s is None or not self.page_per_token_s:
            return 0.0
        ratio = self.gather_lat_s / self.page_per_token_s
        if ratio > self.SLOW_GATHER_RATIO:
            return -1.0                     # gathers hurt → favor pages
        if ratio < self.FAST_GATHER_RATIO:
            return 0.25                     # gathers cheap → keep logging
        return 0.0

    def route(self, nbytes: int) -> str:
        """Record one append of ``nbytes`` and return ``"log"``/``"pages"``."""
        self.hist[nbytes.bit_length()] = \
            self.hist.get(nbytes.bit_length(), 0) + 1
        self._n += 1
        if self._n % self.update_every == 0:
            self._relearn()
        return "log" if nbytes < self.threshold else "pages"

    def _relearn(self) -> None:
        buckets = sorted(self.hist)
        total = sum(self.hist.values())
        # drop noise buckets (<2% of mass) so a stray append can't masquerade
        # as a mode
        buckets = [b for b in buckets
                   if self.hist[b] >= max(total * 0.02, 1)] or buckets
        gap_mid, gap_w = None, 1
        for lo, hi in zip(buckets, buckets[1:]):
            if hi - lo > gap_w:
                gap_w, gap_mid = hi - lo, (lo + hi) / 2
        if gap_mid is not None:
            # bimodal: split at the valley, biased by observed reuse and by
            # the measured gather-latency-vs-page-cost ratio
            reads = self.hot_reads + self.cold_reads
            bias = 0.0
            if reads:
                if self.cold_reads > 0.75 * reads:
                    bias = -0.5        # cold-heavy reuse → favor pages
                elif self.hot_reads > 0.75 * reads:
                    bias = 0.5         # hot-window reuse → favor the log
            bias = max(-1.5, min(1.5, bias + self._latency_bias()))
            self.threshold = int(2 ** (gap_mid + bias))
            return
        mode = max(buckets, key=lambda b: self.hist[b])
        mode_size = 1 << max(mode - 1, 0)
        if mode_size >= self.page_bytes:
            self.threshold = self.page_bytes       # page-sized: route pages
        else:
            self.threshold = min(4 * mode_size, self.page_bytes)


@register_kv_engine("kvhybrid")
class HybridKVCache(_DrainingKV):
    """The combined design: adaptive log/pages routing + sharded drainers.

    Small appends take the log path (1× sequential host write, HBM hot
    window, per-shard background drain into host pages); large appends write
    host pages directly (no redo write for fully covered pages). Coherence
    follows the FS ``nvhybrid`` ownership rule: before the page side takes
    ownership of a sequence's pages, that sequence's drain shard is
    force-drained — log entries always reach the pages before page-side
    writes land on top (log-before-pages ordering).
    """

    def __init__(self, spec: KVSpec, clock: SimClock, *,
                 hbm_budget_bytes: int, hot_window_tokens: int = 256,
                 drain_batch: int = 32, drain_shards: int = 1,
                 threshold_bytes: int = 2048):
        super().__init__(spec, clock, hot_window_tokens=hot_window_tokens,
                         drain_batch=drain_batch, drain_shards=drain_shards,
                         hbm_budget_bytes=hbm_budget_bytes)
        # pages whose pending state the page side owns: seq → {logical}
        self.page_owned: dict[int, set[int]] = {}
        # modeled cost of serving one token from a compacted page — the
        # reference the router's gather-latency feedback compares against
        page_per_token = (HOST_LINK.read_latency / spec.page_tokens
                          + spec.token_bytes / HOST_LINK.read_bw)
        self.router = AdaptiveRouter(threshold_bytes, spec.page_bytes,
                                     page_per_token_s=page_per_token)
        self.stats.update({"routed_log": 0, "routed_pages": 0,
                           "page_appends": 0, "force_drains": 0,
                           "redo_bytes": 0})

    @classmethod
    def from_spec(cls, spec: EngineSpec, kvspec: KVSpec,
                  clock: SimClock) -> "HybridKVCache":
        return cls(kvspec, clock, hbm_budget_bytes=spec.kv_hbm_bytes,
                   hot_window_tokens=spec.kv_hot_window,
                   drain_batch=spec.drain_batch,
                   drain_shards=spec.drain_shards,
                   threshold_bytes=spec.hybrid_threshold)

    @property
    def threshold(self) -> int:
        """Current learned routing threshold in bytes (a gauge, not a
        counter — deliberately not part of ``stats``)."""
        return self.router.threshold

    def _log_takes_page(self, seq: int, logical: int) -> None:
        # the log side owns this page again (reads patch from the log)
        owned = self.page_owned.get(seq)
        if owned:
            owned.discard(logical)

    def _log_owns(self, seq: int, logical: int) -> bool:
        # ownership is what reads trust: once the page side took a page
        # (after the force-drain), the log never patches it again
        return logical not in self.page_owned.get(seq, ())

    def _observe_read(self, seq: int, hot_tokens: int, cold_tokens: int,
                      latency_s: float) -> None:
        self.router.observe_read(seq, hot_tokens, cold_tokens, latency_s)

    def victim_hint(self, candidates: Iterable[int]) -> Optional[int]:
        """Preemption victim from the router's per-sequence reuse histogram:
        the candidate whose reads reuse the hot window least (its history is
        served from pages/disk anyway), ties broken toward the largest HBM
        footprint. ``None`` when no candidate has been read yet — the
        scheduler then falls back to LRU."""
        scored = [(self.router.reuse_score(seq), seq) for seq in candidates]
        if all(score is None for score, _ in scored):
            return None
        # unread sequences score neutral: known-cold beats unknown
        return min(scored, key=lambda sv: (
            0.5 if sv[0] is None else sv[0],
            -self.resident_bytes(sv[1])))[1]

    def _append_pages(self, seq: int, toks: list[np.ndarray]) -> None:
        spec = self.spec
        start = self.seq_len.get(seq, 0)
        end = start + len(toks)
        # ownership handover: this sequence's log entries must reach the
        # pages before the page side writes on top of them
        self._force_drain_seq(seq)
        for i, kv_token in enumerate(toks):
            pos = start + i
            logical = pos // spec.page_tokens
            page_lo = logical * spec.page_tokens
            page_hi = page_lo + spec.page_tokens
            full_page = start <= page_lo and page_hi <= end
            nbytes = spec.token_bytes * spec.num_layers
            if full_page:
                # fully covered page: one sequential write, no redo
                self.clock.charge(HOST_LINK, "write", nbytes,
                                  random_access=False)
            else:
                # partial page: redo append + in-place page write (the
                # paging design's 2× for sub-page writes)
                self.clock.charge(HOST_LINK, "write", nbytes,
                                  random_access=False)
                self.clock.charge(HOST_LINK, "write", nbytes,
                                  random_access=True)
                self.stats["redo_bytes"] += nbytes
            self.stats["host_writes"] += 1
            self._apply(seq, pos, kv_token)
            self.page_owned.setdefault(seq, set()).add(logical)
            self.stats["page_appends"] += 1
            self._hot_push(seq, pos, kv_token)
            self.seq_len[seq] = pos + 1

    def _force_drain_seq(self, seq: int) -> None:
        if self.pending_for(seq):
            super()._force_drain_seq(seq)
            self.stats["force_drains"] += 1

    def _append_tokens(self, seq: int, toks: list[np.ndarray]) -> None:
        nbytes = len(toks) * self.spec.token_bytes * self.spec.num_layers
        route = self.router.route(nbytes)
        if route == "log":
            self.stats["routed_log"] += 1
            self._append_log(seq, toks)
        else:
            self.stats["routed_pages"] += 1
            self._append_pages(seq, toks)
        if not self._batch_depth:
            self._advance(self.clock.now)

    def _drop_seq(self, seq: int) -> None:
        super()._drop_seq(seq)
        self.page_owned.pop(seq, None)

    def _on_release(self, seq: int) -> None:
        self.router.forget_seq(seq)
