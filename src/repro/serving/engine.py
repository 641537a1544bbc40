"""Batched serving engine: continuous-batching decode over the tiered KV
cache (DESIGN.md §2a).

The engine keeps the model's working KV cache in "HBM" (device arrays) and
mirrors every appended token into the tiered cache so sequences can be
preempted/offloaded and restored — the serving translation of the paper's
cache. The tiered mirror is a :class:`repro.core.engines.kv.KVCacheEngine`
constructed through the KV registry from the same :class:`EngineSpec` the
FS tier uses, so a serving config and an FS config are one object. Prefill
mirrors as ONE batched append (a large write — under ``kvhybrid`` it routes
to the page side), decode steps as single-token appends (small writes — the
log side). The mirror's simulated tier-times and amplification stats are
what kvcache_bench reports against the paper's expectations.

``generate()`` runs requests through the continuous-batching
:class:`~repro.serving.scheduler.Scheduler`: requests are admitted into a
running batch, every scheduler tick steps the whole batch through a single
batched ``decode_step``, and sequences are preempted to the disk tier (and
later restored) when the engine's HBM accounting hits its budget.
``generate_sequential()`` keeps the one-request-at-a-time loop as the
reference implementation the scheduler must match token-for-token.

Mirror transfers are sliced **on device**: each decode step moves exactly
one ``(L, 2, K, D)`` float16 token per sequence over the device→host link
(counted in ``stats()["mirror_d2h_bytes"]``), never a whole cache row.

**Mirror-free pooled decode (ISSUE 4, generalized by ISSUE 9).** When the
KV engine owns a device-resident page pool (``paged``) and the model's
:class:`~repro.core.engines.desc.CacheDescriptor` exists, the dense mirror
disappears entirely: admission scatters the prompt's prefilled cache
planes into pool pages on device, every decode step runs the family's
paged kernel over the pool with block-table indirection, and the engine's
block-table/LRU accounting advances through ``prepare_step``/
``commit_step_planes`` with no device→host copy at all:
``mirror_d2h_bytes`` stays **zero** on this path (pinned by test). The
descriptor — not a ``supports_*`` gate — decides the layout: dense GQA
pools ``(k, v)``, int8 pools quantized pages next to their bf16 scale
planes (half the HBM bytes/token), MLA pools the latent ``(c, kr)``
planes, and SSM pools ZERO pages — its fixed-size state rows ride in the
engine (``state_views``/``commit_state``) alongside the block tables.
Engines without a pool (``log``, ``kvhybrid``) and families without a
descriptor (hybrid, encdec) fall back to the mirrored path transparently;
``ServeConfig.paged_decode`` forces either path.

**Fused mixed-batch ticks (ISSUE 5).** The paper's batched-submission
lesson, applied to the tick itself: instead of one batched decode launch
plus N batch=1 prefill-chunk launches, every scheduler tick is exactly ONE
ragged forward (:meth:`ServingEngine.step_batch`) — decode rows contribute
one new token (``q_len = 1``), mid-prefill rows contribute their next
chunk (``q_len ≤ chunk_tokens``), and the ``paged_attention_ragged``
kernel (pooled) or the ragged dense step (mirrored) attends them all in
the same launch with intra-chunk causal masking. Batch width and Qmax pad
up a power-of-two bucketing ladder (padding rows carry ``q_len = 0`` and
are masked end to end, including their pool scatters), so the jitted steps
stop recompiling per width — the tracer's per-program compile counters
(``compiles.jit(step_paged_ragged)`` in ``stats()``) pin it.
``ServeConfig.fuse_ticks=False`` keeps the batch=1-per-chunk baseline
(``kvcache_bench``'s fused gate measures the gap), and model families
without a cache descriptor (hybrid, encdec) fall back to it
transparently.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clock import SimClock
from repro.core.engines import EngineSpec, create_kv_engine
from repro.core.kvcache import KVSpec
from repro.kernels.paged_attention.kernel import ragged_grid_blocks
from repro.serving import batching
from repro.serving.trace import TRACER


@dataclass
class ServeConfig:
    # field order keeps legacy positional construction working: the new
    # engine_spec field comes last
    max_len: int = 512
    design: Optional[str] = None   # legacy switch: "log" | "paged" | name
    page_tokens: int = 16          # geometry (KVSpec): composes with either
    hbm_budget_bytes: Optional[int] = None   # legacy → EngineSpec.kv_hbm_bytes
    hot_window_tokens: Optional[int] = None  # legacy → EngineSpec.kv_hot_window
    greedy: bool = True
    # the shared config object; None → built from the legacy fields above
    engine_spec: Optional[EngineSpec] = None
    # continuous-batching scheduler knobs
    max_batch_seqs: int = 8        # running-batch width cap
    max_batch_tokens: Optional[int] = None   # running-batch token cap
    min_running: int = 1           # preemption floor: progress guarantee
    # mirror-free pooled decode: None = auto (pooled when the engine has a
    # device page pool AND the model family supports paged decode), True =
    # require it (raise if unsupported), False = always mirror
    paged_decode: Optional[bool] = None
    # chunked prefill: prompts longer than this admit chunk by chunk across
    # ticks (None → max_batch_tokens; chunking off when both are None)
    prefill_chunk_tokens: Optional[int] = None
    # fused mixed-batch ticks (ISSUE 5): every scheduler tick is ONE ragged
    # forward over decode rows AND prefill-chunk rows together. False keeps
    # the batch=1-per-chunk baseline (the --no-fuse comparison in
    # kvcache_bench); models without a ragged step fall back automatically.
    fuse_ticks: bool = True
    # forward-progress guard: a row present in the running batch must
    # advance (≥1 token or chunk) within this many consecutive running
    # ticks, else the scheduler raises — the chunk-row starvation pin
    progress_tick_limit: int = 4
    # speculative multi-token decode (ISSUE 7): each running decode row
    # proposes up to k draft tokens per fused tick, verified by the same
    # ragged forward; accepted runs commit, rejected tails roll back.
    # 0 = off. Greedy outputs stay token-identical either way.
    speculate_k: int = 0
    # proposer override: any DraftProposer (serving/speculative.py) — e.g.
    # a small draft model from repro/configs; None → the self-drafting
    # NGramProposer
    draft_proposer: Optional[object] = None
    # fault tolerance (ISSUE 10): a FaultPlan (serving/faults.py) turns on
    # deterministic fault injection — failed/delayed transfers, lost host
    # pages, drainer-shard stalls, a crash at a tick boundary. None = no
    # injection (and zero fault counters).
    fault_plan: Optional[object] = None
    # crash-consistent token journal (serving/journal.py): every scheduler
    # tick appends its committed tokens through the NVMM log tier; after a
    # CrashFault a fresh engine sharing the SAME journal object calls
    # recover() to rebuild and resume. None = no journal.
    journal: Optional[object] = None

    def resolved_spec(self) -> EngineSpec:
        """One EngineSpec no matter which knobs the caller used.

        Mixing a full ``engine_spec`` with the legacy tier knobs raises:
        silently preferring one of the two would run a wrong config (same
        loud-conflict rule as ``CheckpointManager``/``NVCacheFS``).
        """
        legacy = {k: v for k, v in
                  (("design", self.design),
                   ("hbm_budget_bytes", self.hbm_budget_bytes),
                   ("hot_window_tokens", self.hot_window_tokens))
                  if v is not None}
        if self.engine_spec is not None:
            if not isinstance(self.engine_spec, EngineSpec):
                raise TypeError(
                    f"engine_spec must be an EngineSpec, got "
                    f"{type(self.engine_spec).__name__!s}: "
                    f"{self.engine_spec!r}")
            if legacy:
                raise TypeError(
                    f"pass KV-tier parameters inside engine_spec, not as "
                    f"ServeConfig fields (got both a spec and "
                    f"{sorted(legacy)})")
            return self.engine_spec
        return EngineSpec(
            engine=self.design or "log",
            kv_hbm_bytes=(64 << 20 if self.hbm_budget_bytes is None
                          else self.hbm_budget_bytes),
            kv_hot_window=(128 if self.hot_window_tokens is None
                           else self.hot_window_tokens))


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    generated: list = field(default_factory=list)
    done: bool = False
    # when the request was made (``time.perf_counter_ns``): the scheduler's
    # ``serve.queue`` span runs from here to its first admission
    created_ns: int = field(default_factory=time.perf_counter_ns)


class ServingEngine:
    def __init__(self, model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        mcfg = model.cfg
        self.clock = SimClock()
        kv_heads = max(mcfg.num_kv_heads, 1)
        head_dim = max(mcfg.head_dim, 1)
        # the model family's cache-layout descriptor (None → hybrid/encdec:
        # mirror-only). It rides inside KVSpec so a pool-capable engine
        # sizes, allocates and byte-accounts the pool from the SAME plane
        # list the model's paged/ragged steps consume.
        self.desc = model.cache_descriptor(cfg.page_tokens)
        spec = KVSpec(num_layers=mcfg.num_layers, kv_heads=kv_heads,
                      head_dim=head_dim, page_tokens=cfg.page_tokens,
                      desc=self.desc)
        self.tiered = create_kv_engine(cfg.resolved_spec(), spec, self.clock)
        # deterministic fault injection + crash-consistent journal (I10).
        # The injector attaches BEFORE init_pool so the transfer pipeline
        # is constructed with it; the journal's WAL region survives a
        # simulated crash (the object outlives the engine), only its clock
        # is re-attached to this engine's fresh one.
        self.injector = None
        if cfg.fault_plan is not None:
            from repro.serving.faults import FaultInjector
            self.injector = FaultInjector(cfg.fault_plan)
            self.tiered.set_fault_injector(self.injector)
        self.journal = cfg.journal
        if self.journal is not None:
            self.journal.attach_clock(self.clock)
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cfg.max_len))
        self._decode = jax.jit(model.decode_step)
        self._gather_new_kv = jax.jit(batching.gather_new_kv)
        self._gather_prefill_kv = jax.jit(batching.gather_prefill_kv,
                                          static_argnums=2)
        self._gather_kv_range = jax.jit(batching.gather_kv_range,
                                        static_argnums=(2, 3))
        self.mirror_d2h_bytes = 0      # device→host mirror traffic (exact)
        self.sched_stats: dict = {}    # last generate()'s scheduler counters
        # host-facing mirror appends are dense-layout: a pooled engine with
        # a non-dense descriptor (int8/MLA pages, SSM state rows) cannot
        # absorb them, so the sequential reference counts its mirror bytes
        # but skips the tiered append (generate() never mirrors when pooled)
        self._mirror_appends_ok = True
        # ---------------------------------------------- fused mixed-batch tick
        # one ragged forward per tick (decode rows + prefill-chunk rows in
        # the same launch); families without a cache descriptor (hybrid,
        # encdec) keep the batch=1-per-chunk fallback transparently
        self.fused = bool(cfg.fuse_ticks) and model.supports_ragged_step()
        if self.fused:
            self._step_ragged = jax.jit(model.step_ragged)
            self._gather_new_kv_ragged = jax.jit(
                batching.gather_new_kv_ragged, static_argnums=3)
        # launch counters; every batched/fused step buckets its batch width
        # and Qmax to powers of two (pad + mask), and the tracer's
        # per-program compile counters pin that the jits stop recompiling
        self.jit_stats = {"prefill_calls": 0, "step_calls": 0,
                          "fused_steps": 0}
        # ------------------------------------------- mirror-free pooled path
        self.max_pages = -(-cfg.max_len // cfg.page_tokens)
        budget = cfg.resolved_spec().kv_hbm_bytes
        if self.desc is None:
            pool_fits, budget_pages = False, 0
        elif self.desc.has_pages:
            # liveness floor: the pool must hold one max-length sequence
            # plus a reserve page, or a lone running sequence could exhaust
            # it with nothing left to preempt
            budget_pages = budget // self.desc.page_group_bytes
            pool_fits = budget_pages >= self.max_pages + 1
        else:
            # state-row family (SSM): fixed-size rows, need one running row
            # plus one restore in flight
            budget_pages = budget // max(self.desc.seq_state_bytes, 1)
            pool_fits = budget_pages >= 2
        pool_ok = self.tiered.supports_pool() and self.desc is not None
        if cfg.paged_decode and not (pool_ok and pool_fits):
            raise ValueError(
                f"paged_decode=True needs a pool-capable KV engine, a model "
                f"family with a cache descriptor, and an HBM budget of at "
                f"least {self.max_pages + 1} pool pages; got engine="
                f"{self.tiered.engine_name!r} (supports_pool="
                f"{self.tiered.supports_pool()}), family="
                f"{model.cfg.family!r}, budget_pages={budget_pages}")
        self.pooled = (pool_ok and pool_fits) if cfg.paged_decode is None \
            else bool(cfg.paged_decode)
        if self.pooled:
            if self.desc.has_pages and cfg.max_len % cfg.page_tokens:
                raise ValueError(
                    f"pooled decode needs max_len ({cfg.max_len}) to be a "
                    f"multiple of page_tokens ({cfg.page_tokens})")
            # the descriptor already carries each plane's dtype (the dense
            # planes are the model's compute dtype, so pooled decode stays
            # numerically identical to the dense path; int8 pages keep
            # int8 next to their bf16 scale planes)
            self.tiered.init_pool()
            self._mirror_appends_ok = self.desc.kernel == "dense"
            self._decode_paged = jax.jit(model.decode_step_paged)
            self._step_paged_ragged = jax.jit(model.step_paged_ragged)
            self._scatter_prefill = jax.jit(batching.scatter_prefill_planes,
                                            static_argnums=3)
        # the (query tile × KV block) pairs the GQA ragged kernel's launch
        # holds and visits per layer, by the kernel's own block sizes
        self._attn_blocks = None
        if self.pooled and self.desc.kernel in ("dense", "int8"):
            self._attn_blocks = functools.partial(
                ragged_grid_blocks, group=mcfg.num_heads // kv_heads,
                page_tokens=cfg.page_tokens, max_pages=self.max_pages,
                page_bytes=cfg.page_tokens * sum(
                    p.entry_bytes for p in self.desc.paged_planes
                    if p.kind == "kv"))
        # ----------------------------------------- speculative decode (I7)
        # draft-and-verify over the ragged entries: decode rows carry
        # 1 + k query slots, the per-slot logits of the SAME fused forward
        # verify the drafts, and rejected tails roll back (partial commit
        # on the pooled path, truncated mirror transfer on the dense path)
        self.speculate_k = max(int(cfg.speculate_k), 0)
        if self.speculate_k and not self.fused:
            raise ValueError(
                f"speculate_k={self.speculate_k} needs fused ragged ticks "
                f"(fuse_ticks=True and a model family with a ragged step); "
                f"got fuse_ticks={cfg.fuse_ticks}, "
                f"supports_ragged_step={model.supports_ragged_step()}")
        self.proposer = None
        if self.speculate_k:
            if cfg.draft_proposer is not None:
                self.proposer = cfg.draft_proposer
            else:
                from repro.serving.speculative import NGramProposer
                self.proposer = NGramProposer()
        self.spec_stats = {"spec_proposed": 0, "spec_accepted": 0}
        # ------------------------------------------ cross-request prefix cache
        # token-keyed radix index over shared pool pages (ISSUE 6): cache-hit
        # admission splices the block table instead of prefilling. Requires
        # the pooled path; engines without a pool keep sharing off (their
        # admission behavior is unchanged, still token-identical)
        self.prefix_cache = None
        pc_tokens = cfg.resolved_spec().prefix_cache_tokens
        if self.pooled and pc_tokens > 0 and self.desc.has_pages:
            from repro.serving.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(self.tiered,
                                            capacity_tokens=pc_tokens)

    # -------------------------------------------------------------- mirroring
    def _mirror_kv(self, rid: int, cache, pos: int):
        """Mirror the newly appended token's KV into the tiered cache.

        The ``(L, K, D)`` token is sliced and stacked ON DEVICE
        (:func:`batching.gather_new_kv`) so only the single fp16 token
        crosses the device→host link — never the whole padded cache row.
        """
        if "k" not in cache:
            return                      # SSM-family: O(1) state, nothing to page
        tok = np.asarray(self._gather_new_kv(
            cache["k"], cache["v"], jnp.asarray([pos], jnp.int32)))[0]
        self.mirror_d2h_bytes += tok.nbytes
        if self._mirror_appends_ok:
            self.tiered.append(rid, tok)

    def mirror_decode_batch(self, rids: list, cache, positions) -> None:
        """Mirror one decode step's tokens for a whole running batch: one
        on-device gather, ONE device→host transfer of ``(B, L, 2, K, D)``
        fp16, one batched ``append_many`` into the tiered engine. Bucket
        -ladder padding rows (``positions`` may be longer than ``rids``)
        are sliced off ON DEVICE before the transfer, so the byte
        accounting stays exact: one fp16 token per real sequence."""
        if "k" not in cache or not rids:
            return
        toks_dev = self._gather_new_kv(
            cache["k"], cache["v"], jnp.asarray(positions, jnp.int32))
        toks = np.asarray(toks_dev[:len(rids)])
        self.mirror_d2h_bytes += toks.nbytes
        self.tiered.append_many(
            [(rid, toks[i]) for i, rid in enumerate(rids)])

    def _mirror_step_ragged(self, rids: list, cache, ctx, q_lens,
                            qmax: int, committed=None) -> None:
        """Mirror one fused mixed tick's new tokens: ONE on-device ragged
        gather, then at most TWO device→host transfers — the decode rows
        (``q_len == 1``) as exactly one fp16 token each (the PR 3 byte
        accounting, unchanged), and the chunk rows as one
        ``(n_chunk, Qmax, ...)`` block whose only padding is each chunk's
        own Qmax remainder. Per-row appends follow — a chunk row lands as
        one multi-token append, so ``kvhybrid`` still routes it by size.

        ``committed`` (speculative decode) caps each row's transfer at its
        accepted token count: a rejected draft tail is truncated ON DEVICE
        before the block crosses the link, so it never reaches the mirror
        and never inflates the byte accounting."""
        if "k" not in cache or not rids:
            return
        committed = (list(q_lens) if committed is None
                     else [int(c) for c in committed])
        toks_dev = self._gather_new_kv_ragged(
            cache["k"], cache["v"], jnp.asarray(ctx, jnp.int32), qmax)
        dec = [i for i, m in enumerate(committed) if m == 1]
        chk = [i for i, m in enumerate(committed)
               if m > 1 and m == q_lens[i]]
        part = [i for i, m in enumerate(committed) if 1 < m < q_lens[i]]
        items = []
        if dec:
            toks1 = np.asarray(toks_dev[jnp.asarray(dec), 0])
            self.mirror_d2h_bytes += toks1.nbytes  # (n_dec, L, 2, K, D)
            items += [(rids[i], toks1[j]) for j, i in enumerate(dec)]
        if chk:
            toksn = np.asarray(toks_dev[jnp.asarray(chk)])
            self.mirror_d2h_bytes += toksn.nbytes  # (n_chk, qmax, L, 2, K, D)
            items += [(rids[i], toksn[j, :q_lens[i]].transpose(1, 2, 0, 3, 4))
                      for j, i in enumerate(chk)]
        for i in part:   # accepted run of a speculative row, tail dropped
            tk = np.asarray(toks_dev[i, :committed[i]])
            self.mirror_d2h_bytes += tk.nbytes     # (accepted, L, 2, K, D)
            items.append((rids[i], tk.transpose(1, 2, 0, 3, 4)))
        # append in original row order (FIFO drain order is per-seq, but
        # keep the schedule deterministic)
        items.sort(key=lambda kv: rids.index(kv[0]))
        self.tiered.append_many(items)

    def _mirror_prefill(self, rid: int, cache, n: int):
        """Mirror the whole prompt's KV as one batched append (sliced to the
        prompt's ``n`` live tokens on device, cast to fp16 before transfer)."""
        if "k" not in cache or n == 0:
            return
        toks = np.asarray(self._gather_prefill_kv(cache["k"], cache["v"], n))
        self.mirror_d2h_bytes += toks.nbytes
        if self._mirror_appends_ok:
            self.tiered.append(rid, toks)

    # ------------------------------------------------------------- generation
    def prefill_one(self, req: Request, n: Optional[int] = None,
                    tokens: Optional[np.ndarray] = None):
        """Prefill one request at batch=1 (the first ``n`` prompt tokens
        when chunked admission splits it) and land its KV in the tiered
        engine — mirrored as one batched append, or scattered into pool
        pages on device on the mirror-free path. ``tokens`` overrides the
        prompt for re-admission of a shed or crash-recovered row (its
        prompt plus already-committed tokens). Returns (logits, cache row)
        for the scheduler to admit."""
        src = req.prompt if tokens is None else tokens
        toks = src if n is None else src[:n]
        batch = {"tokens": jnp.asarray(toks[None, :])}
        self.jit_stats["prefill_calls"] += 1
        logits, cache = self._prefill(self.params, batch)
        if self.pooled:
            cache = self._pool_admit(req.rid, cache, toks.shape[0])
        else:
            self._mirror_prefill(req.rid, cache, toks.shape[0])
        return logits, cache

    def admit_prefix(self, req: Request):
        """Try a prefix-cache splice for ``req``: on a hit the sequence
        adopts the shared pool pages covering its longest cached prefix —
        ZERO prefill compute for the covered tokens (no ``_prefill`` call,
        no scatter) — and returns ``(cache_row, covered)``; the scheduler
        prefills only ``prompt[covered:]``. None on a miss or when sharing
        is off."""
        if self.prefix_cache is None:
            return None
        covered = self.prefix_cache.match_and_splice(req.rid, req.prompt)
        if covered <= 0:
            return None
        return {"pos": jnp.asarray([covered], jnp.int32)}, covered

    def on_prompt_complete(self, rid: int, prompt: np.ndarray) -> None:
        """A request's FULL prompt is now in the pool: publish its pages
        into the prefix index so later admissions can splice them."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(rid, prompt)

    def _pool_admit(self, rid: int, cache, n: int) -> dict:
        """Move a fresh prompt's prefilled cache into the engine-owned pool
        (one on-device scatter — zero device→host bytes) and shrink the
        row's cache to its position vector. Paged families scatter every
        descriptor plane into pool pages; the state-row family (SSM)
        commits the prompt-final state rows instead — either way the dense
        prefill cache is dropped and the row carries only ``pos``."""
        if n == 0:
            return {"pos": cache["pos"]}
        if not self.desc.has_pages:
            self.tiered.commit_state(
                [rid], [n],
                tuple(cache[p.name] for p in self.desc.seq_planes))
            return {"pos": cache["pos"]}
        phys = self.tiered.alloc_prefill(rid, n)
        pools = self._scatter_prefill(
            self.tiered.pool_views(),
            tuple(cache[p.name] for p in self.desc.paged_planes),
            jnp.asarray(phys, jnp.int32), n)
        self.tiered.commit_prefill_planes(pools, rid, n)
        return {"pos": cache["pos"]}

    def decode_batch(self, rids: list, caches: list, tokens: list,
                     mirrored: bool):
        """One batched single-token decode step over per-sequence cache
        rows (the unfused baseline's batched launch, and the only batched
        path for model families without a ragged step).

        Mirror path: dense batched ``decode_step`` + one device→host token
        transfer per sequence, width-bucketed with dummy rows so
        ``_decode`` stops recompiling per batch width. Pooled path: the
        ragged step at ``q_len = 1`` — its masked scatter is what lets
        bucket-ladder padding rows exist without ever touching the shared
        device pool. Returns (logits, new cache rows).
        """
        if self.pooled:
            logit_rows, rows, _ = self.step_batch(
                rids, caches, [np.asarray([t], np.int32) for t in tokens],
                mirrored, fused=False)
            return jnp.concatenate(logit_rows, axis=0), rows
        B = len(caches)
        pad = batching.bucket_pow2(B) - B
        batch = batching.concat_rows(caches + [caches[0]] * pad)
        positions = batch["pos"]
        tok_arr = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)[:, None]
        self.jit_stats["step_calls"] += 1
        logits, batch = self._decode(self.params, batch, tok_arr, positions)
        self.mirror_decode_batch(rids if mirrored else [], batch,
                                 np.asarray(positions))
        return logits[:B], [batching.split_row(batch, i) for i in range(B)]

    def publish_plan(self, rids: list, n_tokens: list) -> int:
        """Scheduler lookahead (ISSUE 8): next tick's planned batch — rids
        with the token slots each will claim. Pooled engines forward it to
        the async tiering pipeline, which starts H2D fault-ins for any
        spilled page of a planned row so ``prepare_step`` finds the
        transfer already in flight; everywhere else it is a no-op."""
        if not self.pooled:
            return 0
        return self.tiered.prefetch(rids, n_tokens)

    def can_step_fused(self, rids: list, n_tokens: list) -> bool:
        """Can this tick's mixed batch be placed in one fused step?
        Pooled engines answer through :meth:`KVCacheEngine.can_place_step`
        (prepare_step pins the whole batch, so a tight pool may need a
        preemption first — the scheduler's pre-step guard); the mirrored
        path always fits."""
        if not self.pooled:
            return True
        return self.tiered.can_place_step(rids, n_tokens)

    def _verify_drafts(self, logits, tok_rows, q_lens, spec) -> list:
        """Greedy draft verification against the SAME fused forward's
        per-slot logits. Row ``i``'s tokens are ``[t0, d1..ds]``
        (``s = spec[i]`` trailing drafts): slot ``j``'s argmax is the
        greedy token after consuming token ``j``, so draft ``d_{j+1}`` is
        accepted iff it equals ``argmax(slot j)`` AND every earlier draft
        was — the longest accepted prefix is exactly the sequential greedy
        run. Returns per-row committed counts (``1 + accepted``; chunk and
        plain decode rows commit everything)."""
        B = len(tok_rows)
        committed = list(q_lens)
        need = [i for i in range(B) if spec[i] > 0]
        if not need:
            return committed
        args = np.asarray(jnp.argmax(logits[:B], axis=-1))   # (B, Qb)
        TRACER.count("host_syncs")
        for i in need:
            q, s = q_lens[i], spec[i]
            acc = 0
            for j in range(s):
                if int(tok_rows[i][q - s + j]) != int(args[i, q - s + j - 1]):
                    break
                acc += 1
            committed[i] = q - s + acc
            self.spec_stats["spec_proposed"] += s
            self.spec_stats["spec_accepted"] += acc
        return committed

    def step_batch(self, rids: list, caches: list, tok_rows: list,
                   mirrored: bool, fused: bool = True,
                   spec_lens: Optional[list] = None):
        """ONE fused forward over a mixed ragged batch — the tentpole
        launch: decode rows carry 1 new token (plus up to ``speculate_k``
        draft tokens when speculation is on), prefill-chunk rows up to
        ``chunk_tokens``, and all of them attend in the same jitted step
        (``model.step_paged_ragged`` over the device pool, or
        ``model.step_ragged`` over the dense mirror). Batch width and Qmax
        pad up the power-of-two ladder; padding rows ride with
        ``q_len = 0`` and are masked end to end.

        ``spec_lens[i]`` marks how many TRAILING tokens of ``tok_rows[i]``
        are unverified drafts: they scatter speculatively (the same masked
        ``mode="drop"`` discipline that protects padding), are verified
        against this forward's own per-slot logits, and the rejected tail
        rolls back before anything else sees it — partial ``commit_step``
        on the pooled path, truncated mirror transfer + a rewound ``pos``
        on the dense path.

        Returns ``(logit_rows, new_rows, committed)``: per-row logits for
        each row's committed slots (``(1, committed[i], V)`` — the LAST
        slot is what the next tick's argmax reads), the new per-row
        caches, and the per-row committed token counts.
        """
        B = len(rids)
        q_lens = [len(t) for t in tok_rows]
        spec = [0] * B if spec_lens is None else [int(s) for s in spec_lens]
        Bb = batching.bucket_pow2(B)
        Qb = batching.bucket_pow2(max(q_lens))
        tokens = np.zeros((Bb, Qb), np.int32)
        for i, t in enumerate(tok_rows):
            tokens[i, :len(t)] = t
        qarr = np.zeros(Bb, np.int32)
        qarr[:B] = q_lens
        tok_j = jnp.asarray(tokens)
        qlen_j = jnp.asarray(qarr)
        if fused:       # the unfused pooled decode reuses this entry at
            self.jit_stats["fused_steps"] += 1   # q_len=1; don't count it
            TRACER.count("rows.pad", Bb - B)
            TRACER.count("slots.all", Bb * Qb)
            TRACER.count("slots.pad", Bb * Qb - sum(q_lens))

        if self.pooled and not self.desc.has_pages:
            return self._step_state_batch(rids, caches, tok_rows, tok_j,
                                          qlen_j, q_lens, spec, Bb, Qb)
        if self.pooled:
            names = [p.name for p in self.desc.paged_planes]
            # fault containment (ISSUE 10 satellite): any exception between
            # prepare_step and commit_step — a lost host page surfacing as
            # LostPageError, a drift check, a kernel error — must rewind
            # the pages prepare_step allocated for this tick, or a poisoned
            # tick pins them forever (the pool leak the regression test in
            # tests/test_tiering.py hunts)
            try:
                with TRACER.span("serve.prepare"):
                    tbl, ctx = self.tiered.prepare_step(rids, q_lens,
                                                        self.max_pages)
                    model_pos = np.concatenate([np.asarray(c["pos"])
                                                for c in caches])
                    TRACER.count("host_syncs", B)
                    if not np.array_equal(ctx, model_pos):
                        raise RuntimeError(
                            f"pool/table drift: engine lengths "
                            f"{ctx.tolist()} != model positions "
                            f"{model_pos.tolist()}")
                    tbl_p = np.zeros((Bb, self.max_pages), np.int32)
                    tbl_p[:B] = tbl
                    ctx_p = np.zeros(Bb, np.int32)
                    ctx_p[:B] = ctx
                    if fused and self._attn_blocks is not None:
                        every, live = self._attn_blocks(qarr, ctx_p + qarr,
                                                        qmax=Qb)
                        TRACER.count("attn.blocks.all", every)
                        TRACER.count("attn.blocks.live", live)
                    cache = {"block_table": jnp.asarray(tbl_p)}
                    for n, v in zip(names, self.tiered.pool_views()):
                        cache["pool_" + n] = v
                self.jit_stats["step_calls"] += 1
                with TRACER.span("serve.launch"):
                    logits, out = self._step_paged_ragged(
                        self.params, cache, tok_j, jnp.asarray(ctx_p),
                        qlen_j)
                with TRACER.span("serve.commit"):
                    committed = self._verify_drafts(logits, tok_rows,
                                                    q_lens, spec)
                    self.tiered.commit_step_planes(
                        tuple(out["pool_" + n] for n in names), rids,
                        committed, prepared=q_lens)
            except Exception:
                self.tiered.abort_step(rids)
                raise
            with TRACER.span("serve.rows"):
                new_rows = [
                    {"pos": out["pos"][i:i + 1]} if committed[i] == q_lens[i]
                    else {"pos": jnp.asarray([int(ctx[i]) + committed[i]],
                                             jnp.int32)}
                    for i in range(B)]
                logit_rows = [logits[i:i + 1, :committed[i]]
                              for i in range(B)]
            return logit_rows, new_rows, committed
        batch = batching.concat_rows(caches + [caches[0]] * (Bb - B))
        ctx = batch["pos"]
        self.jit_stats["step_calls"] += 1
        logits, nbatch = self._step_ragged(self.params, batch, tok_j,
                                           ctx, qlen_j)
        committed = self._verify_drafts(logits, tok_rows, q_lens, spec)
        if mirrored:
            self._mirror_step_ragged(rids, nbatch, ctx, q_lens, Qb,
                                     committed)
        nbatch = self._select_state_slots(nbatch, committed, B)
        new_rows = [batching.split_row(nbatch, i) for i in range(B)]
        ctx_np = np.asarray(ctx)
        for i in range(B):
            if committed[i] != q_lens[i]:
                # rewind past the rejected tail: its dense-cache KV is
                # masked (kv_pos > pos) and overwritten in place by the
                # row's next committed tokens
                new_rows[i]["pos"] = jnp.asarray(
                    [int(ctx_np[i]) + committed[i]], jnp.int32)
        logit_rows = [logits[i:i + 1, :committed[i]] for i in range(B)]
        return logit_rows, new_rows, committed

    def _step_state_batch(self, rids: list, caches: list, tok_rows: list,
                          tok_j, qlen_j, q_lens: list, spec: list,
                          Bb: int, Qb: int):
        """Fused ragged tick for the state-row (SSM) family: the engine's
        pool holds per-sequence state rows instead of pages, so the tick
        reads them back as batched views, runs the ragged state scan (which
        emits PER-SLOT states), and commits each row's committed slot —
        committing an earlier slot IS the speculative rollback, and a
        fully-rejected or padding row (``committed == 0``) commits nothing.
        Zero device→host bytes, same as the paged branch."""
        B = len(rids)
        ctx = np.concatenate([np.asarray(c["pos"]) for c in caches])
        TRACER.count("host_syncs", B)
        eng_len = [int(self.tiered.seq_len.get(r, 0)) for r in rids]
        if eng_len != [int(c) for c in ctx]:
            raise RuntimeError(
                f"state-row drift: engine lengths {eng_len} != model "
                f"positions {ctx.tolist()}")
        ctx_p = np.zeros(Bb, np.int32)
        ctx_p[:B] = ctx
        # bucket-ladder padding rows replicate row 0's state: they carry
        # q_len = 0, so their outputs are discarded and nothing commits
        views = self.tiered.state_views(list(rids) + [rids[0]] * (Bb - B))
        cache = {p.name: v for p, v in zip(self.desc.seq_planes, views)}
        self.jit_stats["step_calls"] += 1
        logits, out = self._step_paged_ragged(
            self.params, cache, tok_j, jnp.asarray(ctx_p), qlen_j)
        committed = self._verify_drafts(logits, tok_rows, q_lens, spec)
        states = []
        for j, p in enumerate(self.desc.seq_planes):
            steps = out[p.name + "_steps"]       # (L, Qmax, B, ...)
            states.append(jnp.stack(
                [steps[:, committed[i] - 1, i] if committed[i] > 0
                 else views[j][:, i] for i in range(B)], axis=1))
        self.tiered.commit_state(rids, committed, tuple(states))
        new_rows = [{"pos": jnp.asarray([int(ctx[i]) + committed[i]],
                                        jnp.int32)} for i in range(B)]
        logit_rows = [logits[i:i + 1, :committed[i]] for i in range(B)]
        return logit_rows, new_rows, committed

    def _select_state_slots(self, batch: dict, committed: list, B: int):
        """Mirror-path twin of the state commit: fold the ragged SSM step's
        per-slot state stacks (``<plane>_steps``, shaped
        ``(L, Qmax, B, ...)``) down to each row's committed slot before the
        batch splits back into rows. Rows with ``committed == 0`` keep the
        step's INPUT state (the rolled-back row re-plans next tick); the
        ``_steps`` stacks never leave this method."""
        step_keys = [k for k in batch if k.endswith("_steps")]
        if not step_keys:
            return batch
        out = {k: v for k, v in batch.items() if k not in step_keys}
        for key in step_keys:
            name = key[:-len("_steps")]
            steps = batch[key]
            out[name] = jnp.stack(
                [steps[:, committed[i] - 1, i] if i < B and committed[i] > 0
                 else batch[name][:, i] for i in range(steps.shape[2])],
                axis=1)
        return out

    def extend_one(self, rid: int, cache, toks: np.ndarray, start: int,
                   mirrored: bool):
        """UNFUSED fallback (``fuse_ticks=False`` or a family without a
        ragged step): process ``toks`` additional prompt tokens for one
        admitted row, each token through the decode path at batch=1; the
        chunk's KV lands in the tiered engine as ONE batched append
        (mirror path) or directly in its pool pages (pooled path —
        per-token page allocation, still zero device→host bytes). The
        fused path replaces all of this with the chunk riding inside
        :meth:`step_batch`. Returns (logits, cache) positioned after the
        chunk."""
        logits = None
        if self.pooled and not self.desc.has_pages:
            # state-row family: check the rows out of the engine, run the
            # chunk through decode_step at batch=1, commit the final state
            views = self.tiered.state_views([rid])
            pc = {"pos": cache["pos"]}
            for p, v in zip(self.desc.seq_planes, views):
                pc[p.name] = v
            for t in toks:
                self.jit_stats["step_calls"] += 1
                logits, pc = self._decode(
                    self.params, pc, jnp.asarray([[int(t)]], jnp.int32),
                    pc["pos"])
            self.tiered.commit_state(
                [rid], [len(toks)],
                tuple(pc[p.name] for p in self.desc.seq_planes))
            return logits, {"pos": pc["pos"]}
        if self.pooled:
            names = [p.name for p in self.desc.paged_planes]
            for t in toks:
                tbl, _ = self.tiered.prepare_decode([rid], self.max_pages)
                pc = {"pos": cache["pos"],
                      "block_table": jnp.asarray(tbl)}
                for n, v in zip(names, self.tiered.pool_views()):
                    pc["pool_" + n] = v
                self.jit_stats["step_calls"] += 1
                logits, out = self._decode_paged(
                    self.params, pc, jnp.asarray([[int(t)]], jnp.int32),
                    cache["pos"])
                self.tiered.commit_step_planes(
                    tuple(out["pool_" + n] for n in names), [rid], [1])
                cache = {"pos": out["pos"]}
            return logits, cache
        for t in toks:
            self.jit_stats["step_calls"] += 1
            logits, cache = self._decode(
                self.params, cache, jnp.asarray([[int(t)]], jnp.int32),
                cache["pos"])
        if mirrored and len(toks):
            kv = np.asarray(self._gather_kv_range(
                cache["k"], cache["v"], start, start + len(toks)))
            self.mirror_d2h_bytes += kv.nbytes
            self.tiered.append(rid, kv)
        return logits, cache

    def degraded(self) -> bool:
        """True once persistent async transfer faults flipped the tiering
        pipeline to its synchronous fallback (the degradation ladder's
        second rung — see engines/README.md)."""
        pipe = getattr(self.tiered, "_pipeline", None)
        return bool(pipe is not None and pipe.degraded)

    def generate(self, requests: list[Request]) -> list[Request]:
        """Continuous-batching decode: all requests share one running batch,
        stepped together and preempted/restored under HBM pressure. Greedy
        outputs are token-identical to :meth:`generate_sequential`."""
        from repro.serving.scheduler import Scheduler
        sched = Scheduler(self, requests)
        try:
            sched.run()
        finally:
            # a CrashFault abandons the run mid-tick, but the scheduler
            # counters gathered so far are still what the caller inspects
            self.sched_stats = sched.stats.as_dict()
        self.tiered.flush_transfers()   # run-end drain: sim_time_s includes
        return requests                 # in-flight transfer tails

    def recover(self, requests: list[Request]) -> list[Request]:
        """Crash recovery (ISSUE 10): replay the journal this engine shares
        with the crashed one, rebuild each request's committed stream, and
        resume decoding the unfinished rows through the normal scheduler —
        re-admission prefills ``prompt + committed`` so greedy decode
        continues exactly where the last durable tick stopped. The result
        is token-identical to an uninterrupted run (property-tested).
        ``requests`` must be fresh Request objects carrying the original
        prompts/rids; their ``generated`` fields are overwritten from the
        journal."""
        if self.journal is None:
            raise RuntimeError(
                "recover() needs the crashed run's journal: construct this "
                "engine with ServeConfig(journal=<same ServingJournal>)")
        state, _last_tick = self.journal.replay()
        pending = []
        for req in requests:
            toks = state.get(req.rid, [])
            req.generated = [int(t) for t in toks[:req.max_new]]
            req.done = len(req.generated) >= req.max_new
            if not req.done:
                pending.append(req)
        if pending:
            self.generate(pending)
        return requests

    def generate_sequential(self, requests: list[Request]) -> list[Request]:
        """Sequential reference: one request at a time, batch=1 decode over
        the dense cache with the mirrored tiered append — ALWAYS, even on a
        pool-enabled engine, because this is the reference the pooled path
        must match token-for-token."""
        for req in requests:
            batch = {"tokens": jnp.asarray(req.prompt[None, :])}
            logits, cache = self._prefill(self.params, batch)
            self._mirror_prefill(req.rid, cache, req.prompt.shape[0])
            for _ in range(req.max_new):
                nxt = int(jnp.argmax(logits[:, -1], -1)[0])
                req.generated.append(nxt)
                pos = cache["pos"]
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray([[nxt]], jnp.int32), pos)
                self._mirror_kv(req.rid, cache, int(pos[0]))
            req.done = True
        return requests

    def stats(self) -> dict:
        """The engine's counters, its last scheduler's, the journal's, the
        KV engine's and the process tracer's (``serve/trace.py``)."""
        journal = {} if self.journal is None else dict(self.journal.stats)
        return {"sim_time_s": self.clock.now,
                "mirror_d2h_bytes": self.mirror_d2h_bytes,
                **self.jit_stats, **self.spec_stats, **self.sched_stats,
                **journal, **self.tiered.stats, **TRACER.counters()}
