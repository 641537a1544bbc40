"""Cross-request prefix sharing (ISSUE 6): the serving-tier battery.

Five suites lock the prefix cache down:

* **trie** — the token radix tree's exact-find / refcount / eviction
  surface the cache is built on (unit level, no model);
* **splice** — re-admitting a cached prompt splices shared pool pages:
  the covered prefix costs ZERO prefill calls (pinned via ``jit_stats``),
  the hit counters move, and the tokens stay identical to the sequential
  reference;
* **copy-on-write** — concurrent duplicate prompts alias the mid-page
  boundary page; the first divergent decode write copies it (``cow_copies``
  moves) and nobody's tokens change;
* **pressure** — sharing under a tight HBM budget: preemption fires, every
  stat counter (including the new prefix counters) stays monotone tick by
  tick, and the output still matches sequential;
* **release** — churn leaves no page refs behind (pool drains back to
  free + idle-index), and ``release()`` forgets router state even for
  preempted sequences (the ``_on_release`` hook regression).
"""
import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core import SimClock, create_kv_engine
from repro.core.engines import EngineSpec
from repro.core.kvcache import KVSpec
from repro.core.radix import TokenRadixTree
from repro.models import build_model
from repro.serving import Request, Scheduler, ServeConfig, ServingEngine
from repro.serving.trace import TRACER

ARCH = "internlm2-1.8b-smoke"
MAX_LEN = 48
MAX_NEW = 6
PROMPT_LEN = 10          # % page_tokens(4) = 2: the last chunk is mid-page


@pytest.fixture(scope="module")
def lm():
    cfg = get_config(ARCH)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _token_bytes(mcfg) -> int:
    return mcfg.num_layers * 2 * mcfg.num_kv_heads * mcfg.head_dim * 2


def _engine(lm, engine="paged", *, share_tokens=4096, hbm_bytes=64 << 20,
            max_batch_seqs=4, chunk=None):
    cfg, model, params = lm
    return ServingEngine(model, params, ServeConfig(
        max_len=MAX_LEN, page_tokens=4,
        engine_spec=EngineSpec(engine=engine, kv_hbm_bytes=hbm_bytes,
                               kv_hot_window=8, drain_shards=2,
                               prefix_cache_tokens=share_tokens),
        max_batch_seqs=max_batch_seqs, prefill_chunk_tokens=chunk))


def _prompt(cfg, seed=0, n=PROMPT_LEN):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, n, dtype=np.int32)


def _sequential(lm, prompts, max_new=MAX_NEW):
    reqs = [Request(rid=i, prompt=p.copy(), max_new=max_new)
            for i, p in enumerate(prompts)]
    _engine(lm, "log", share_tokens=0).generate_sequential(reqs)
    return [list(r.generated) for r in reqs]


# ------------------------------------------------------------------- trie
def test_token_trie_find_and_match():
    t = TokenRadixTree()
    n1 = t.insert((1, 2, 3, 4), "a")
    n2 = t.insert((1, 2, 3, 4, 5, 6), "b")
    assert t.find((1, 2, 3, 4)) is n1
    assert t.find((1, 2, 3)) is None          # interior node, no value
    assert t.find((9,)) is None
    assert t.lookup((1, 2, 3, 4, 5, 6)) == "b"
    # match returns every value node on the path, shallowest first
    assert t.match((1, 2, 3, 4, 5, 6, 7)) == [n1, n2]
    assert t.match((1, 2, 9)) == []


def test_token_trie_refcounts_gate_eviction():
    t = TokenRadixTree()
    n1 = t.insert((1, 2), "a")
    n2 = t.insert((1, 2, 3), "b")
    t.acquire(n2)
    # a referenced leaf is not evictable; an interior value node never is
    assert not t.evictable(n2)
    assert not t.evictable(n1)                # subtree_values == 2
    t.release(n2)
    assert t.evictable(n2)
    t.remove(n2)
    assert t.evictable(n1)                    # now a refcount-0 leaf
    with pytest.raises(RuntimeError):
        t.release(n2)                         # underflow is loud


# ----------------------------------------------------------------- splice
def test_cached_readmission_skips_prefill_and_matches_sequential(lm):
    """The zero-prefill pin: the second admission of an identical prompt
    splices pool pages — ``prefill_calls`` does not move, the hit counters
    do, and the tokens equal the sequential reference."""
    cfg, _, _ = lm
    prompt = _prompt(cfg)
    want = _sequential(lm, [prompt])[0]
    eng = _engine(lm)
    assert eng.prefix_cache is not None

    r0 = Request(rid=0, prompt=prompt.copy(), max_new=MAX_NEW)
    eng.generate([r0])
    s1 = eng.stats()
    assert s1["prefix_hits"] == 0 and s1["prefill_calls"] >= 1

    r1 = Request(rid=1, prompt=prompt.copy(), max_new=MAX_NEW)
    eng.generate([r1])
    s2 = eng.stats()
    assert s2["prefix_hits"] == 1
    # a full duplicate is covered up to len-1 (one pending token keeps the
    # first-logits contract); none of the covered tokens re-prefill
    assert s2["prefix_tokens_reused"] == PROMPT_LEN - 1
    assert s2["prefill_calls"] == s1["prefill_calls"]
    assert s2["mirror_d2h_bytes"] == 0        # still the mirror-free path
    assert r0.generated == want and r1.generated == want


def test_shared_prefix_families_splice_across_tails(lm):
    """Distinct tails behind one hot prefix: later family members cover the
    page-aligned prefix chunks and only prefill their private tail."""
    cfg, _, _ = lm
    rng = np.random.default_rng(1)
    fam = rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)   # 2 full pages
    prompts = [np.concatenate([fam, rng.integers(0, cfg.vocab_size, n,
                                                 dtype=np.int32)])
               for n in (3, 5, 2)]
    want = _sequential(lm, prompts)
    eng = _engine(lm, max_batch_seqs=1)       # strictly one at a time
    for i, p in enumerate(prompts):
        eng.generate([Request(rid=i, prompt=p.copy(), max_new=MAX_NEW)])
    s = eng.stats()
    assert s["prefix_hits"] == 2              # every admission after the 1st
    assert s["prefix_tokens_reused"] == 2 * len(fam)
    reqs = [Request(rid=10 + i, prompt=p.copy(), max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)                        # warm trie, batched this time
    for r, w in zip(reqs, want):
        assert r.generated == w


# ---------------------------------------------------------- copy-on-write
def test_concurrent_duplicates_cow_on_boundary_page(lm):
    """Duplicates admitted into ONE batch alias the mid-page boundary page;
    the first decode write while others still trust it must copy, and every
    row's tokens stay identical to the sequential reference."""
    cfg, _, _ = lm
    prompt = _prompt(cfg, seed=2)
    prompts = [prompt, prompt, prompt, _prompt(cfg, seed=3)]
    want = _sequential(lm, prompts)
    eng = _engine(lm)
    reqs = [Request(rid=i, prompt=p.copy(), max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    s = eng.stats()
    assert s["prefix_hits"] >= 2              # both later duplicates spliced
    assert s["cow_copies"] >= 1
    assert s["shared_pages"] >= 1
    for r, w in zip(reqs, want):
        assert r.done and r.generated == w, r.rid


# --------------------------------------------------------------- pressure
def test_sharing_under_pressure_stays_monotone_and_token_identical(lm):
    """Tight budget + chunked prefill + duplicates: preemption fires, the
    full stat surface (prefix counters included) is monotone tick by tick,
    and sharing never changes a token."""
    cfg, model, _ = lm
    prompt = _prompt(cfg, seed=4)
    prompts = [prompt, prompt, _prompt(cfg, seed=5, n=12), prompt]
    want = _sequential(lm, prompts)
    # the smallest budget that still takes the POOLED path (max_pages + 1
    # pool pages — any less and sharing is off by construction): the
    # warm-up row fits without spilling its prefix pages, four growing
    # rows do not
    mcfg = model.cfg
    group = (mcfg.num_layers * 2 * 4 * mcfg.num_kv_heads * mcfg.head_dim
             * np.dtype(model.compute_dtype).itemsize)
    eng = _engine(lm, hbm_bytes=(MAX_LEN // 4 + 1) * group, chunk=5)
    assert eng.pooled and eng.prefix_cache is not None
    warm = Request(rid=99, prompt=prompt.copy(), max_new=MAX_NEW)
    eng.generate([warm])                      # publishes the prompt's pages
    assert warm.generated == want[0]
    reqs = [Request(rid=i, prompt=p.copy(), max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    sched = Scheduler(eng, reqs)
    prev = eng.stats()
    for k in ("prefix_hits", "prefix_tokens_reused", "cow_copies",
              "shared_pages"):
        assert k in prev                      # uniform key set, all engines
    while sched.tick():
        cur = eng.stats()
        # only the tracer's counters may appear (each on its first use)
        assert set(cur) >= set(prev)
        assert set(cur) - set(prev) <= set(TRACER.counters())
        for k, v in prev.items():
            assert cur[k] >= v, k
        prev = cur
    assert eng.tiered.stats["preempts"] >= 1
    assert eng.tiered.stats["prefix_hits"] >= 1
    for r, w in zip(reqs, want):
        assert r.done and r.generated == w, r.rid


@pytest.mark.parametrize("engine", ("log", "kvhybrid"))
def test_sharing_flag_is_noop_for_unpooled_engines(lm, engine):
    """``prefix_cache_tokens`` on a log-structured engine must change
    nothing: no cache object, zero hit counters, identical tokens."""
    cfg, _, _ = lm
    prompt = _prompt(cfg, seed=6)
    prompts = [prompt, prompt]
    want = _sequential(lm, prompts)
    eng = _engine(lm, engine)
    assert eng.prefix_cache is None
    reqs = [Request(rid=i, prompt=p.copy(), max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    s = eng.stats()
    assert s["prefix_hits"] == 0 and s["shared_pages"] == 0
    for r, w in zip(reqs, want):
        assert r.generated == w


# ---------------------------------------------------------------- release
def test_churn_releases_every_shared_page(lm):
    """After a sharing-heavy run completes, no page holds a live user ref:
    the pool is exactly free pages + idle index pages, and pressure is
    back to zero (idle index pages are reclaimable headroom)."""
    cfg, _, _ = lm
    prompt = _prompt(cfg, seed=7)
    eng = _engine(lm)
    for round_ in range(3):
        reqs = [Request(rid=10 * round_ + i, prompt=prompt.copy(),
                        max_new=MAX_NEW) for i in range(3)]
        eng.generate(reqs)
    kv = eng.tiered
    assert not kv.page_users                  # no live user refs anywhere
    assert len(kv.free_pages) + kv._idle_index_pages() == kv.pool_pages
    assert kv.pressure() == 0.0
    assert eng.stats()["prefix_hits"] >= 1    # the index did real work


def test_release_forgets_router_state_even_when_preempted():
    """The ``_on_release`` hook regression: releasing a PREEMPTED sequence
    must still forget the adaptive router's per-seq reuse state (the old
    kvhybrid-only forget sat on the active-release branch and leaked)."""
    spec = KVSpec(num_layers=2, kv_heads=2, head_dim=4, page_tokens=4)
    kv = create_kv_engine(
        EngineSpec(engine="kvhybrid", kv_hbm_bytes=1 << 14, kv_hot_window=4,
                   drain_shards=2), spec, SimClock())
    rng = np.random.default_rng(0)
    for seq in (0, 1):
        kv.append(seq, rng.standard_normal(
            (spec.num_layers, 2, 6, spec.kv_heads,
             spec.head_dim)).astype(np.float16))
        kv.read(seq, layer=0)                 # materialize reuse state
    assert 0 in kv.router.seq_reuse and 1 in kv.router.seq_reuse
    kv.preempt(0)
    kv.release(0)                             # preempted-release branch
    kv.release(1)                             # active-release branch
    assert 0 not in kv.router.seq_reuse
    assert 1 not in kv.router.seq_reuse
