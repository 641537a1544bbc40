"""Continuous-batching scheduler: the serving-tier battery (ISSUE 3).

Four suites lock the scheduler down:

* **equivalence** — greedy batched decode is token-identical to the
  sequential reference for every KV engine, any admission order, and any
  batch width (raggedness/padding never leaks into logits);
* **preemption round-trip** — a preempt→restore cycle mid-decode changes no
  generated token for any engine (host/disk spills are exact);
* **forced pressure** — an HBM-budget-constrained run completes all
  requests, observes at least one preempt/restore cycle in the engine
  stats, and every stat counter stays monotone tick by tick;
* **starvation guard** — every admitted request finishes even when the
  budget forces constant preemption churn.
"""
import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.engines import EngineSpec
from repro.models import build_model
from repro.serving import Request, Scheduler, ServeConfig, ServingEngine
from repro.serving.trace import TRACER

ARCH = "internlm2-1.8b-smoke"
KV_ENGINES = ("paged", "log", "kvhybrid")
MAX_LEN = 48
PROMPT_LENS = (8, 12, 8)     # two distinct lengths bound jit compiles
MAX_NEW = 6


@pytest.fixture(scope="module")
def lm():
    cfg = get_config(ARCH)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _token_bytes(mcfg) -> int:
    """One mirrored fp16 KV token, all layers."""
    return mcfg.num_layers * 2 * mcfg.num_kv_heads * mcfg.head_dim * 2


def _requests(cfg, seed=0, max_new=MAX_NEW):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
                    max_new=max_new)
            for i, n in enumerate(PROMPT_LENS)]


def _engine(lm, engine, *, hbm_bytes=64 << 20, max_batch_seqs=4,
            max_batch_tokens=None, chunk=None, fuse=True):
    cfg, model, params = lm
    return ServingEngine(model, params, ServeConfig(
        max_len=MAX_LEN, page_tokens=4,
        engine_spec=EngineSpec(engine=engine, kv_hbm_bytes=hbm_bytes,
                               kv_hot_window=8, drain_shards=2),
        max_batch_seqs=max_batch_seqs, max_batch_tokens=max_batch_tokens,
        prefill_chunk_tokens=chunk, fuse_ticks=fuse))


@pytest.fixture(scope="module")
def reference(lm):
    """Sequential greedy tokens per rid — engine-independent (the tiered
    mirror never feeds back into the model)."""
    cfg, _, _ = lm
    reqs = _requests(cfg)
    _engine(lm, "log").generate_sequential(reqs)
    return {r.rid: list(r.generated) for r in reqs}


# ------------------------------------------------------------- equivalence
@pytest.mark.parametrize("engine", KV_ENGINES)
@pytest.mark.parametrize("max_batch_seqs", [1, 2, 4])
def test_batched_decode_token_identical_to_sequential(lm, reference, engine,
                                                      max_batch_seqs):
    cfg, _, _ = lm
    reqs = _requests(cfg)
    eng = _engine(lm, engine, max_batch_seqs=max_batch_seqs)
    eng.generate(reqs)
    for r in reqs:
        assert r.done
        assert r.generated == reference[r.rid], (engine, max_batch_seqs,
                                                 r.rid)


@pytest.mark.parametrize("engine", KV_ENGINES)
def test_admission_order_never_changes_tokens(lm, reference, engine):
    """Submitting the same requests in any order gives each request the
    same tokens (batch composition must not leak into any row)."""
    cfg, _, _ = lm
    for order in ((2, 0, 1), (1, 2, 0)):
        reqs = _requests(cfg)
        eng = _engine(lm, engine, max_batch_seqs=2)
        eng.generate([reqs[i] for i in order])
        for r in reqs:
            assert r.generated == reference[r.rid], (engine, order, r.rid)


def test_max_batch_tokens_caps_admission(lm, reference):
    """A token cap admits fewer sequences at once but changes no output."""
    cfg, _, _ = lm
    reqs = _requests(cfg)
    eng = _engine(lm, "log", max_batch_tokens=PROMPT_LENS[0] + MAX_NEW + 1)
    eng.generate(reqs)
    assert eng.sched_stats["sched_peak_running"] == 1
    for r in reqs:
        assert r.generated == reference[r.rid]


def test_max_batch_tokens_enforced_as_batch_grows(lm, reference):
    """Decode growth past the token cap preempts (admission headroom is one
    step; the cap holds over the whole run) — and changes no output."""
    cfg, _, _ = lm
    reqs = _requests(cfg)
    # submit the two 8-token prompts first: both admit (8 + 8+1 <= 20)
    # and then grow to 14 tokens each, crossing the cap mid-decode
    eng = _engine(lm, "log", max_batch_tokens=20)
    sched = Scheduler(eng, [reqs[0], reqs[2], reqs[1]])
    while sched.tick():
        assert sum(r.length for r in sched.running) <= 20
    assert sched.stats.preempts >= 1
    for r in reqs:
        assert r.generated == reference[r.rid]


def test_zero_max_new_matches_sequential(lm):
    """max_new=0 requests finish without decoding a single token on both
    paths (the batched step must not run before the finish check)."""
    cfg, _, _ = lm
    for runner in ("generate", "generate_sequential"):
        reqs = _requests(cfg, max_new=0)
        reqs[1].max_new = 2              # mixed batch: others still decode
        eng = _engine(lm, "log")
        getattr(eng, runner)(reqs)
        assert [len(r.generated) for r in reqs] == [0, 2, 0], runner
        assert all(r.done for r in reqs), runner


# ------------------------------------------- preempt/restore round-trip
@pytest.mark.parametrize("engine", KV_ENGINES)
def test_preempt_restore_mid_decode_preserves_tokens(lm, reference, engine):
    """A tiny HBM budget forces preemption mid-decode; spilled sequences
    must come back bit-identical (same greedy tokens as unconstrained)."""
    cfg, model, _ = lm
    budget = 10 * _token_bytes(model.cfg)     # ~10 resident tokens total
    reqs = _requests(cfg)
    eng = _engine(lm, engine, hbm_bytes=budget)
    eng.generate(reqs)
    stats = eng.stats()
    assert stats["preempts"] >= 1, engine
    assert stats["restores"] >= 1, engine
    for r in reqs:
        assert r.done
        assert r.generated == reference[r.rid], (engine, r.rid)


# --------------------------------------------------------- forced pressure
@pytest.mark.parametrize("engine", KV_ENGINES)
def test_forced_pressure_preempts_and_stats_stay_monotone(lm, engine):
    cfg, model, _ = lm
    reqs = _requests(cfg)
    eng = _engine(lm, engine, hbm_bytes=10 * _token_bytes(model.cfg))
    sched = Scheduler(eng, reqs)
    prev = eng.stats()
    while sched.tick():
        cur = eng.stats()
        # only the tracer's counters may appear (each on its first use)
        assert set(cur) >= set(prev)
        assert set(cur) - set(prev) <= set(TRACER.counters())
        for k, v in prev.items():
            assert cur[k] >= v, (engine, k)
        prev = cur
    assert eng.tiered.stats["preempts"] >= 1
    assert eng.tiered.stats["restores"] >= 1
    assert sched.stats.preempts == eng.tiered.stats["preempts"]
    assert all(r.done and len(r.generated) == MAX_NEW for r in reqs)


def test_pressure_surface_is_scheduler_sufficient(lm):
    """The scheduler only ever needs pressure()/resident_bytes()/
    victim_hint() — check the surface behaves: pressure hits 1.0 under the
    tight budget, drops after the run releases everything."""
    cfg, model, _ = lm
    eng = _engine(lm, "kvhybrid", hbm_bytes=10 * _token_bytes(model.cfg))
    assert eng.tiered.pressure() == 0.0
    eng.generate(_requests(cfg))
    assert eng.sched_stats["sched_preempts"] >= 1
    assert eng.tiered.pressure() == 0.0       # all released at the end
    assert eng.tiered.hbm_limit_bytes() > 0


# --------------------------------------------------- jit-shape bucketing pins
STEP_PROGRAMS = ("compiles.jit(step_paged_ragged)", "compiles.jit(step_ragged)")


def _step_compiles(stats) -> int:
    """Backend compiles of the fused step programs, from the tracer's
    per-program counters in the engine's stats."""
    return sum(stats.get(k, 0) for k in STEP_PROGRAMS)


@pytest.mark.parametrize("engine", ("paged", "log"))
def test_jit_bucketing_pins_compile_counts(lm, reference, engine):
    """The recompile pin: batch width and Qmax bucket to the power-of-two
    ladder, so a run over chunked prompts compiles a handful of step
    programs — and a SECOND schedule with a different batch width (4 vs 3,
    same bucket) plus the same chunking compiles ZERO new step programs.
    (The second schedule's new prompt length does compile a new prefill
    program and eager ops, so the total compile count is not the pin.)"""
    cfg, _, _ = lm
    reqs = _requests(cfg)
    eng = _engine(lm, engine, chunk=5)
    s0 = eng.stats()
    eng.generate(reqs)                    # widths 3→bucket 4; chunks 5/2/1
    s1 = eng.stats()
    assert _step_compiles(s1) - _step_compiles(s0) <= 4, s1
    for r in reqs:
        assert r.generated == reference[r.rid]
    rng = np.random.default_rng(3)
    reqs4 = [Request(rid=i,
                     prompt=rng.integers(0, cfg.vocab_size, 12,
                                         dtype=np.int32), max_new=MAX_NEW)
             for i in range(4)]
    eng.generate(reqs4)                   # width 4 → the same bucket
    s2 = eng.stats()
    assert _step_compiles(s2) == _step_compiles(s1), (
        "a new batch width inside an existing bucket must not recompile")
    assert s2["step_calls"] > s1["step_calls"]


def test_jit_bucketing_across_chunk_sizes(lm, reference):
    """Chunk budgets that bucket to the same Qmax share compiles: chunk 5
    and chunk 7 both pad to Qmax 8, so the second engine-warm run of either
    compiles no step program the first didn't."""
    cfg, _, _ = lm
    eng = _engine(lm, "log", chunk=7)
    eng.generate(_requests(cfg))
    base = _step_compiles(eng.stats())
    # rerun with the same engine: everything is warm
    reqs = _requests(cfg)
    eng.generate(reqs)
    assert _step_compiles(eng.stats()) == base
    for r in reqs:
        assert r.generated == reference[r.rid]


# --------------------------------------------------------- starvation guard
@pytest.mark.parametrize("engine", KV_ENGINES)
def test_chunk_rows_progress_under_preemption_churn(lm, reference, engine):
    """The chunk-row starvation pin (ISSUE 5 satellite): chunked prompts
    under a budget that preempts constantly — every row that sits in the
    running batch must advance ≥1 chunk or token per tick (the scheduler's
    forward-progress guard raises otherwise), every request finishes, and
    no token moves."""
    cfg, model, _ = lm
    reqs = _requests(cfg)
    eng = _engine(lm, engine, hbm_bytes=10 * _token_bytes(model.cfg),
                  chunk=3)
    eng.generate(reqs)                    # must not trip the progress guard
    s = eng.stats()
    assert s["preempts"] >= 1, engine
    assert s["sched_prefill_chunks"] >= 2
    assert s["sched_stalled_row_ticks"] == 0
    for r in reqs:
        assert r.done and r.generated == reference[r.rid], engine


@pytest.mark.slow
@pytest.mark.parametrize("engine", KV_ENGINES)
def test_every_admitted_request_finishes(lm, engine):
    """Churn case: more requests than batch slots, budget small enough to
    preempt constantly — every request still completes with exactly
    max_new tokens (min_running guarantees per-tick progress)."""
    cfg, model, _ = lm
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               PROMPT_LENS[i % 2],
                                               dtype=np.int32), max_new=4)
            for i in range(6)]
    eng = _engine(lm, engine, hbm_bytes=10 * _token_bytes(model.cfg),
                  max_batch_seqs=2)
    eng.generate(reqs)
    assert all(r.done and len(r.generated) == 4 for r in reqs), engine
    assert eng.sched_stats["sched_finished"] == 6
    assert eng.sched_stats["sched_admitted"] == 6
