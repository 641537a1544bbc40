"""KV-cache tiering benchmark (DESIGN.md §2a): the paper's comparison at the
serving call-site, enumerated over the KV engine registry. Prefill bursts +
decode appends + periodic full-history gathers per engine × workload;
reports simulated tier time, write amplification, DMA traffic, and (for
``kvhybrid``) the learned routing split.

The ``serve`` and ``prefill_heavy`` workloads are the serving-scale regime:
a Poisson arrival process through a continuous-batching loop (the
model-free twin of the serving scheduler) with preemption when the engine's
HBM accounting crosses its budget — they additionally report throughput,
p50/p99 request latency, preempt/restore counts, the pool hit rate, and the
device→host mirror bytes the pooled path saves, per engine.
``prefill_heavy`` is the long-prompt Poisson mix where fused mixed-batch
ticks matter most. Pool-capable engines (``paged``) run the serve workloads
over their device-resident page pool by default; ``--no-pool`` forces
everyone onto the host-mirror path. ``--smoke`` shrinks everything to CI
size.

When a serve-style workload runs, the bench ALSO runs the model-backed
fused-vs-unfused tick comparison (the real ``ServingEngine`` +
``Scheduler`` over the smoke model on a prefill-heavy request set, fused
mixed-batch ticks vs the batch=1-per-chunk baseline) and writes everything
to a stable ``BENCH_serve.json`` at the repo root so the serving perf
trajectory is tracked across PRs. ``--fused-gate`` (CI) exits nonzero if
the fused path is not faster than the ``fuse_ticks=False`` baseline.

``--speculate-k K`` additionally runs the model-backed draft-and-verify
comparison (ISSUE 7): the real ServingEngine on a decode-heavy request
set with ``speculate_k=K`` vs speculation off, recorded under
``speculative`` in BENCH_serve.json. ``--spec-gate`` (CI) exits nonzero
unless the runs are token-identical AND more than one committed token
rides each decode row-launch. The same flag makes the serve-workload
twins commit ``1 + a ∈ [1, 1+K]`` tokens per decode step, keeping their
pool-pressure sizing honest for speculative serving.

``--families all`` (ISSUE 9) runs the model-backed per-family comparison:
every cache-descriptor family (dense GQA, MLA, int8 KV, MoE, SSM) through
the real ServingEngine, pooled fused mirror-free vs the same engine forced
onto the host-mirror path — recorded under ``families`` in
BENCH_serve.json (merged by design × workload × family).
``--family-gate`` (CI) exits nonzero unless every family is
token-identical and mirror-free on the pooled path, beats the mirror
baseline >= 5x on *simulated* decode throughput wherever the mirror
actually moves bytes, and int8 holds <= 0.55x the fp16 pool bytes/token.

``--async-tiering`` runs the sync-vs-async transfer-pipeline comparison
(ISSUE 8): the serve-workload twin on a deliberately tight page pool —
steady spill/fault traffic — once with synchronous transfers and once
with the background pipeline + lookahead prefetch, plus a model-backed
token-identity check (async scheduling must not change a single output
token, and its fault-conservation invariant must hold exactly). Recorded
under ``tiering`` in BENCH_serve.json. ``--tiering-gate`` (CI) exits
nonzero unless async beats sync on *simulated* throughput (deterministic,
like every hard gate here) with ``prefetch_hits > 0`` and
``stall_ticks_saved > 0``.

``--faults`` runs the fault-tolerance benchmark (ISSUE 10): a seeded
chaos run — transfer attempts failed/delayed at ``--fault-rate`` (~1e-2)
over a deliberately tight pool — plus the model-backed crash-at-tick-k
recovery sweep through the NVMM token journal. Recorded under ``faults``
in BENCH_serve.json. ``--fault-gate`` (CI) exits nonzero unless the chaos
run is byte-identical to the fault-free run with the exact conservation
law ``prefetch_hits + pool_faults + retried_faults == fault-free
pool_faults`` and nonzero injected/retried faults, and every
crash-at-tick-k recovery is token-identical to the uninterrupted run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import (ServeWorkload, kv_workloads,
                               prefill_heavy_workload, run_kv_workload,
                               run_serve_workload, serve_workloads)
from repro.core import SimClock
from repro.core.engines import EngineSpec, create_kv_engine, list_kv_engines
from repro.core.kvcache import KVSpec


STEP_PROGRAMS = ("jit(step_paged_ragged)", "jit(step_ragged)",
                 "jit(decode_step_paged)", "jit(decode_step)")


def _step_compiles(stats: dict) -> int:
    """Backend compiles of the jitted step programs so far in this process
    (the tracer's per-program counters in ``ServingEngine.stats()``)."""
    return sum(stats.get("compiles." + p, 0) for p in STEP_PROGRAMS)


def _pool_hit_rate(stats: dict):
    """Fraction of KV reuse served from the fast tier: pool residency for
    pooled engines, HBM LRU hits for host-paged, hot-window hits for the
    log designs. None when the workload never exercised the fast tier."""
    if stats.get("pool_hits") or stats.get("pool_faults"):
        hits, misses = stats["pool_hits"], stats["pool_faults"]
    elif stats.get("hbm_hits") or stats.get("hbm_misses"):
        hits, misses = stats["hbm_hits"], stats["hbm_misses"]
    else:
        hits = stats.get("hot_hits", 0)
        misses = stats.get("patches", 0) + stats.get("host_reads", 0)
    total = hits + misses
    return hits / total if total else None


def bench(engine: str, *, layers=8, kv_heads=8, head_dim=128, tokens=512,
          workload="decode", drain_shards=1, seed=0, smoke=False,
          pool=True, speculate_k=0) -> dict:
    kvspec = KVSpec(num_layers=layers, kv_heads=kv_heads, head_dim=head_dim,
                    page_tokens=16)
    clock = SimClock()
    budget = 2 << 20
    if workload in serve_workloads():
        wl = dataclasses.replace(serve_workloads()[workload], seed=seed,
                                 speculate_k=speculate_k)
        if smoke:
            wl = wl.smoke()
        # the budget must hold MORE than one worst-case prompt, or a single
        # long-prompt request saturates it alone and the twin never reaches
        # the concurrency the preemption path needs (prefill_heavy's
        # prompts are far longer than serve's; 1.25 prompts keeps the
        # squeeze binding either way)
        per_token = kvspec.token_bytes * layers
        budget = max(budget, int(1.25 * max(wl.prompt_tokens) * per_token))
    spec = EngineSpec(engine=engine, kv_hbm_bytes=budget, kv_hot_window=128,
                      drain_shards=drain_shards)
    kv = create_kv_engine(spec, kvspec, clock)
    pooled = False
    if workload in serve_workloads():
        if pool and kv.supports_pool():
            # pool floor: max_batch_seqs - 1 max-length sequences
            # co-resident plus a decode reserve page per batch slot — a
            # full-width batch of worst-case sequences still overflows (so
            # the preemption path is exercised), but a pool smaller than
            # the steady working set would measure page thrash, not the
            # design
            if wl.hot_prefixes:
                # prefix sharing shrinks the steady working set — the hot
                # prompt mass is resident ONCE — so the full-prompt-per-row
                # floor below would leave the pool so roomy the preemption
                # path never fires; use the preset-tuned sharing floor
                # instead (see ServeWorkload.pool_floor_pages)
                min_pages = wl.pool_floor_pages
            else:
                max_seq = max(wl.prompt_tokens) + max(wl.decode_tokens)
                seq_pages = -(-max_seq // kvspec.page_tokens)
                min_pages = (max(wl.max_batch_seqs - 1, 2) * seq_pages
                             + wl.max_batch_seqs)
            budget_pages = spec.kv_hbm_bytes // (kvspec.page_bytes * layers)
            kv.init_pool(pages=max(budget_pages, min_pages))
            pooled = True
        serve = run_serve_workload(kv, kvspec, wl, clock)
        serve["speculate_k"] = wl.speculate_k
        appended = serve.pop("appended_tokens")
        per_token = kvspec.token_bytes * layers
        serve["pool_hit_rate"] = _pool_hit_rate(kv.stats)
        # bytes a dense HBM mirror would have moved device→host for the
        # same token stream — zero is saved on the mirror path
        serve["mirror_d2h_saved_bytes"] = appended * per_token if pooled \
            else 0
    else:
        by_name = {w.name: w for w in kv_workloads(tokens)}
        if workload not in by_name:
            raise ValueError(
                f"unknown workload {workload!r}; choose from "
                f"{', '.join([*by_name, *serve_workloads()])}")
        wl = dataclasses.replace(by_name[workload], seed=seed)
        appended = run_kv_workload(kv, kvspec, wl)
        serve = {}
    host_w = clock.bytes_moved("host", "write")
    host_r = clock.bytes_moved("host", "read")
    return {"design": engine, "workload": wl.name, "pooled": pooled,
            "smoke": smoke,
            "drain_shards": drain_shards, "sim_time_s": clock.now,
            "host_write_bytes": host_w, "host_read_bytes": host_r,
            "write_amplification": host_w / (
                appended * kvspec.token_bytes * layers),
            **serve, **kv.stats}


def bench_fused_ticks(*, smoke=False, arch="internlm2-1.8b-smoke", seed=0,
                      fuse=None) -> dict:
    """Model-backed fused-vs-unfused tick comparison (the tentpole's
    acceptance measurement): the real ServingEngine + Scheduler over the
    smoke model on a prefill-heavy request set — long prompts admitted
    chunk by chunk, short completions — once with fused mixed-batch ticks
    and once with the batch=1-per-chunk baseline (``fuse_ticks=False``).

    Each path runs twice and times the second (warm-jit) pass, so the
    comparison measures per-tick launch structure, not compile time. Also
    reports the deterministic launch accounting: model step calls per
    generated+prefilled token (the fused path's structural win).
    """
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServeConfig, ServingEngine

    cfg = get_config(arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    n_req = 4 if smoke else 6
    chunk = 8
    prompt_lens = [int(x) for x in rng.choice(
        (24, 40) if smoke else (32, 48, 64), n_req)]
    max_new = 4 if smoke else 8
    max_len = max(prompt_lens) + max_new + 1
    max_len += -max_len % 8
    page_tokens = 8

    def run(fuse_ticks: bool) -> dict:
        # ONE engine for both reps: jax.jit caches live on the engine's
        # wrapper objects, so only same-engine reuse makes rep 1 a warm
        # measurement of per-tick launch structure rather than compiles
        eng = ServingEngine(model, params, ServeConfig(
            max_len=max_len, page_tokens=page_tokens,
            engine_spec=EngineSpec(engine="paged",
                                   kv_hbm_bytes=256 << 20),
            max_batch_seqs=4, prefill_chunk_tokens=chunk,
            fuse_ticks=fuse_ticks))

        def one_pass():
            reqs = [Request(rid=i,
                            prompt=rng.integers(0, cfg.vocab_size,
                                                prompt_lens[i],
                                                dtype=np.int32),
                            max_new=max_new) for i in range(n_req)]
            t0 = time.perf_counter()
            eng.generate(reqs)
            return time.perf_counter() - t0

        compiled0 = _step_compiles(eng.stats())
        one_pass()                      # rep 0: compile every step shape
        calls_warm = eng.stats()["step_calls"]
        wall = one_pass()               # rep 1: warm, identical schedule
        s = eng.stats()
        step_calls = s["step_calls"] - calls_warm     # the timed pass only
        tokens = sum(prompt_lens) + n_req * max_new
        return {"fused": eng.fused, "wall_s": wall,
                "tokens": tokens, "ticks": s["sched_ticks"],
                "step_calls": step_calls,
                "step_compiles": _step_compiles(s) - compiled0,
                "prefill_chunks": s["sched_prefill_chunks"],
                "tokens_per_s": tokens / max(wall, 1e-9),
                "tokens_per_launch": tokens / max(step_calls, 1)}

    rows = {}
    if fuse in (None, True):
        rows["fused"] = run(True)
    if fuse in (None, False):
        rows["unfused"] = run(False)
    if "fused" in rows and "unfused" in rows:
        rows["speedup_wall"] = (rows["fused"]["tokens_per_s"]
                                / max(rows["unfused"]["tokens_per_s"], 1e-9))
        rows["launch_ratio"] = (rows["unfused"]["step_calls"]
                                / max(rows["fused"]["step_calls"], 1))
    rows["config"] = {"arch": arch, "requests": n_req,
                      "prompt_lens": prompt_lens, "max_new": max_new,
                      "chunk_tokens": chunk, "smoke": smoke}
    return rows


def bench_speculative(*, smoke=False, arch="internlm2-1.8b-smoke", seed=0,
                      k=4) -> dict:
    """Model-backed draft-and-verify comparison (ISSUE 7's acceptance
    measurement): the real ServingEngine + Scheduler over the smoke model
    on a decode-heavy request set — short prompts, long completions, the
    regime speculation exists for — once with ``speculate_k=k`` and once
    with speculation off. Both runs must produce identical tokens (greedy
    draft-and-verify is exact); the win is structural: committed decode
    tokens per decode row-launch, ``(decode_rows + spec_accepted) /
    decode_rows`` — exactly 1.0 with speculation off, > 1.0 iff verified
    drafts actually ride existing launches. Wall clock is recorded too,
    but the CI gate (``--spec-gate``) reads only the deterministic ratio.

    Each path runs twice on one engine and measures the second (warm-jit)
    pass, same discipline as :func:`bench_fused_ticks`. The untrained
    smoke model's greedy argmax falls into repetitive loops — which is
    precisely the traffic the self-drafting n-gram proposer feeds on, so
    acceptance here is deterministic, not a tuning accident.
    """
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServeConfig, ServingEngine

    cfg = get_config(arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    n_req = 3 if smoke else 4
    prompt_lens = [int(x) for x in rng.choice((8, 12), n_req)]
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in prompt_lens]
    max_new = 24 if smoke else 48
    max_len = max(prompt_lens) + max_new + 1
    max_len += -max_len % 8

    def run(kk: int) -> dict:
        eng = ServingEngine(model, params, ServeConfig(
            max_len=max_len, page_tokens=8,
            engine_spec=EngineSpec(engine="paged", kv_hbm_bytes=256 << 20),
            max_batch_seqs=4, speculate_k=kk))

        def one_pass():
            reqs = [Request(rid=i, prompt=prompts[i].copy(),
                            max_new=max_new) for i in range(n_req)]
            t0 = time.perf_counter()
            eng.generate(reqs)
            return time.perf_counter() - t0, [list(r.generated)
                                              for r in reqs]

        one_pass()                      # rep 0: compile every step shape
        s0 = eng.stats()                # engine counters are cumulative;
        wall, tokens = one_pass()       # scheduler counters are per-pass
        s1 = eng.stats()
        decode_rows = s1["sched_decode_rows"]
        accepted = s1["spec_accepted"] - s0["spec_accepted"]
        proposed = s1["spec_proposed"] - s0["spec_proposed"]
        committed = sum(len(t) for t in tokens)
        return {"speculate_k": kk, "wall_s": wall,
                "generated_tokens": committed,
                "ticks": s1["sched_ticks"],
                "step_calls": s1["step_calls"] - s0["step_calls"],
                "decode_rows": decode_rows,
                "spec_proposed": proposed, "spec_accepted": accepted,
                "acceptance_rate": accepted / max(proposed, 1),
                "accepted_tokens_per_launch":
                    (decode_rows + accepted) / max(decode_rows, 1),
                "tokens_per_s": committed / max(wall, 1e-9),
                "_tokens": tokens}

    spec = run(k)
    base = run(0)
    rows = {"speculative": spec, "baseline": base,
            "token_identical": spec.pop("_tokens") == base.pop("_tokens"),
            "speedup_wall": (spec["tokens_per_s"]
                             / max(base["tokens_per_s"], 1e-9)),
            "launch_ratio": (base["step_calls"]
                             / max(spec["step_calls"], 1)),
            "config": {"arch": arch, "requests": n_req,
                       "prompt_lens": prompt_lens, "max_new": max_new,
                       "speculate_k": k, "smoke": smoke}}
    return rows


def bench_async_tiering(*, smoke=False, arch="internlm2-1.8b-smoke",
                        seed=0) -> dict:
    """Sync-vs-async tier-transfer comparison (ISSUE 8's acceptance
    measurement), in two parts.

    **Twin part** (the gated numbers): the model-free serve twin on a page
    pool sized well below the batch working set, so every step spills and
    every gather faults. Sync charges each D2H/H2D on the foreground
    clock; async drains them through the background pipeline with the
    scheduler's lookahead prefetch hiding fault latency. Both runs move
    the same tokens, so the simulated-throughput ratio isolates exactly
    the transfer stalls — a deterministic quantity, unlike wall clock.

    **Model part** (the safety check): the real ServingEngine + Scheduler
    on a tight pool with speculation on, async vs sync. The pipeline is
    timing-only by design — allocation and spill decisions are identical
    in both modes — so the runs must be token-identical and must satisfy
    the exact conservation law ``prefetch_hits + pool_faults ==
    sync pool_faults`` (Scheduler admission is clock-free, unlike the
    twin's Poisson arrivals, which is why conservation is only asserted
    here)."""
    kvspec = KVSpec(num_layers=8, kv_heads=8, head_dim=128, page_tokens=16)
    wl = ServeWorkload(name="tiering", requests=6 if smoke else 12,
                       mean_interarrival_tokens=8.0,
                       prompt_tokens=(32, 48), decode_tokens=(24, 48),
                       max_batch_seqs=4, gather_every=4, seed=seed)
    max_seq = max(wl.prompt_tokens) + max(wl.decode_tokens)
    seq_pages = -(-max_seq // kvspec.page_tokens)
    # tight on purpose: far below the serve floor (batch working set is
    # ~max_batch_seqs * seq_pages), so spill/fault traffic is steady — this
    # measures the transfer pipeline, the serve rows measure the design
    pages = 2 * seq_pages + wl.max_batch_seqs

    def twin(async_tiering: bool) -> dict:
        clock = SimClock()
        spec = EngineSpec(engine="paged",
                          kv_hbm_bytes=pages * kvspec.page_bytes
                          * kvspec.num_layers,
                          async_tiering=async_tiering)
        kv = create_kv_engine(spec, kvspec, clock)
        kv.init_pool(pages=pages)
        out = run_serve_workload(kv, kvspec, wl, clock)
        out["async_tiering"] = async_tiering
        out["sim_time_s"] = clock.now
        for key in ("pool_faults", "pool_page_spills", "async_spills",
                    "prefetch_hits", "stall_ticks_saved"):
            out[key] = kv.stats[key]
        return out

    sync = twin(False)
    async_ = twin(True)
    rows = {"sync": sync, "async": async_,
            "speedup_sim": (async_["throughput_tok_per_s"]
                            / max(sync["throughput_tok_per_s"], 1e-9)),
            "stall_s_removed": sync["sim_time_s"] - async_["sim_time_s"]}

    # ---- model-backed token identity + exact fault conservation --------
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServeConfig, ServingEngine

    cfg = get_config(arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    n_req = 3 if smoke else 4
    prompt_lens = [int(x) for x in rng.choice((12, 20), n_req)]
    max_new = 12 if smoke else 24
    max_len = max(prompt_lens) + max_new + 1
    max_len += -max_len % 8
    page_tokens = 8
    mcfg = model.cfg
    group_bytes = (mcfg.num_layers * 2 * page_tokens
                   * max(mcfg.num_kv_heads, 1) * max(mcfg.head_dim, 1)
                   * np.dtype(model.compute_dtype).itemsize)
    # just above the liveness floor (one max-length sequence + reserve):
    # the 4-row batch overflows constantly, so admission spills pages the
    # next prepare_step must fault back — the prefetch target
    tight = (-(-max_len // page_tokens) + 3) * group_bytes

    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in prompt_lens]

    def run(async_tiering: bool) -> dict:
        eng = ServingEngine(model, params, ServeConfig(
            max_len=max_len, page_tokens=page_tokens,
            engine_spec=EngineSpec(engine="paged", kv_hbm_bytes=tight,
                                   async_tiering=async_tiering),
            max_batch_seqs=4, speculate_k=2))
        reqs = [Request(rid=i, prompt=prompts[i].copy(), max_new=max_new)
                for i in range(n_req)]
        eng.generate(reqs)
        s = eng.stats()
        return {"async_tiering": async_tiering,
                "tokens": [list(r.generated) for r in reqs],
                "pool_faults": s["pool_faults"],
                "prefetch_hits": s["prefetch_hits"],
                "stall_ticks_saved": s["stall_ticks_saved"],
                "sim_time_s": s["sim_time_s"]}

    m_sync = run(False)
    m_async = run(True)
    rows["model"] = {
        "sync": {k: v for k, v in m_sync.items() if k != "tokens"},
        "async": {k: v for k, v in m_async.items() if k != "tokens"},
        "token_identical": m_sync["tokens"] == m_async["tokens"],
        "fault_conservation":
            m_async["prefetch_hits"] + m_async["pool_faults"]
            == m_sync["pool_faults"]}
    rows["config"] = {"arch": arch, "twin_pool_pages": pages,
                      "requests": n_req, "prompt_lens": prompt_lens,
                      "max_new": max_new, "smoke": smoke}
    return rows


def bench_families(*, smoke=False, seed=0, families="all") -> list:
    """Model-backed per-family serving comparison (ISSUE 9's acceptance
    measurement): every cache-descriptor family — dense GQA, MLA, int8 KV,
    MoE, SSM — through the real ServingEngine + Scheduler, pooled fused
    mirror-free vs the SAME engine forced onto the host-mirror path
    (``paged_decode=False``). Both runs must be token-identical; the win is
    the DETERMINISTIC simulated tier time (the mirror path charges every
    device→host KV byte on the sim clock, the pooled path charges none), so
    the ratio survives noisy CI runners. The SSM mirror baseline moves zero
    mirror bytes by construction (its state rides in the batch rows, there
    is no growing KV to mirror), so its ratio is recorded as None and the
    gate checks mirror-freedom + token identity only."""
    import dataclasses as dc

    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServeConfig, ServingEngine

    def fam_model(fam):
        if fam == "mla":
            cfg = dc.replace(get_config("deepseek-v2-236b-smoke"),
                             family="attn_dense", moe=None)
            return cfg, build_model(cfg, remat=False)
        if fam == "int8":
            cfg = get_config("internlm2-1.8b-smoke")
            return cfg, build_model(cfg, remat=False, kv_cache_dtype="int8")
        if fam == "ssm":
            cfg = get_config("mamba2-1.3b-smoke")
            return cfg, build_model(cfg, remat=False)
        if fam == "moe":
            cfg = get_config("arctic-480b-smoke")
            # no-drop capacity: expert routing stays exact under batching,
            # so token identity is a hard assertion, not a tolerance
            cfg = dc.replace(cfg, moe=dc.replace(
                cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
            return cfg, build_model(cfg, remat=False)
        cfg = get_config("internlm2-1.8b-smoke")
        return cfg, build_model(cfg, remat=False)

    all_fams = ["dense", "mla", "int8", "moe", "ssm"]
    fams = all_fams if families == "all" else families.split(",")
    unknown = set(fams) - set(all_fams)
    if unknown:
        raise ValueError(f"unknown families {sorted(unknown)}; choose from "
                         f"{all_fams}")
    page_tokens = 8
    rows = []
    for fam in fams:
        cfg, model = fam_model(fam)
        params = model.init(jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        n_req = 3 if smoke else 4
        prompt_lens = [int(x) for x in rng.choice((8, 12), n_req)]
        prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                   for n in prompt_lens]
        max_new = 8 if smoke else 16
        max_len = max(prompt_lens) + max_new + 1
        max_len += -max_len % page_tokens

        def run(paged_decode):
            eng = ServingEngine(model, params, ServeConfig(
                max_len=max_len, page_tokens=page_tokens,
                engine_spec=EngineSpec(engine="paged",
                                       kv_hbm_bytes=256 << 20),
                max_batch_seqs=n_req, paged_decode=paged_decode))
            reqs = [Request(rid=i, prompt=prompts[i].copy(),
                            max_new=max_new) for i in range(n_req)]
            t0 = time.perf_counter()
            eng.generate(reqs)
            wall = time.perf_counter() - t0
            s = eng.stats()
            return {"pooled": eng.pooled, "fused": eng.fused,
                    "wall_s": wall, "sim_time_s": s["sim_time_s"],
                    "mirror_d2h_bytes": s["mirror_d2h_bytes"],
                    "_tokens": [list(r.generated) for r in reqs]}

        pooled = run(None)
        mirror = run(False)
        desc = model.cache_descriptor(page_tokens)
        # the mirror baseline's sim clock carries exactly the device→host
        # bytes the pooled path never moves; same tokens both runs, so the
        # sim-throughput ratio is the inverse sim-time ratio (capped so an
        # all-resident pooled run with sim_time 0 stays JSON-finite)
        ratio = (min(mirror["sim_time_s"] / max(pooled["sim_time_s"], 1e-9),
                     1e6)
                 if mirror["mirror_d2h_bytes"] else None)
        row = {"design": "paged", "workload": "serve", "family": fam,
               "smoke": smoke, "planes": list(desc.plane_names),
               "generated_tokens": sum(len(t) for t in pooled["_tokens"]),
               "token_identical":
                   pooled.pop("_tokens") == mirror.pop("_tokens"),
               "pooled": pooled, "mirror": mirror,
               "mirror_d2h_saved_bytes": mirror["mirror_d2h_bytes"],
               "decode_tput_sim_ratio": ratio,
               "bytes_per_token":
                   desc.token_group_bytes or desc.seq_state_bytes}
        if fam == "int8":
            fp16 = (cfg.num_layers * 2 * max(cfg.num_kv_heads, 1)
                    * max(cfg.head_dim, 1) * 2)
            row["fp16_bytes_per_token"] = fp16
            row["bytes_per_token_vs_fp16"] = row["bytes_per_token"] / fp16
        rows.append(row)
    return rows


def bench_faults(*, smoke=False, arch="internlm2-1.8b-smoke", seed=0,
                 fault_rate=1e-2) -> dict:
    """Fault-tolerance benchmark (ISSUE 10's acceptance measurement), in
    two legs.

    **Chaos leg** (engine level, where transfer faults are real): a fixed
    append/read schedule over a deliberately tight page pool, synchronous
    fault-free vs async under a seeded FaultPlan failing/delaying ~1% of
    transfer attempts. The schedule is clock-free, so placement is
    identical and the conservation law is exact: every read must come back
    byte-identical, ``prefetch_hits + pool_faults + retried_faults`` must
    equal the fault-free run's ``pool_faults``, and the injected failures
    must show up as nonzero ``transfer_retries``.

    **Recovery leg** (model-backed): the real ServingEngine on a tight
    pool with speculation on, journaling every tick, crashed at each tick
    k of a sweep with the same chaos rates underneath — then a FRESH
    engine sharing the journal recovers. Every recovered stream must be
    token-identical to the uninterrupted fault-free run; the sweep also
    records the durable-token count at the crash and the recovery's
    simulated time."""
    from repro.serving.faults import CrashFault, FaultInjector, FaultPlan
    from repro.serving.journal import ServingJournal

    # ---- chaos leg: deterministic KV drive, tight pool ------------------
    kvspec = KVSpec(num_layers=2, kv_heads=2, head_dim=8, page_tokens=4)
    pool_pages, n_seqs, steps = 6, 3, 40 if smoke else 120

    def kv_chaos(async_tiering: bool, plan) -> tuple:
        clock = SimClock()
        kv = create_kv_engine(
            EngineSpec(engine="paged", kv_hbm_bytes=1 << 30,
                       async_tiering=async_tiering), kvspec, clock)
        kv.init_pool(pages=pool_pages)
        if plan is not None:
            kv.set_fault_injector(FaultInjector(plan))
        rng = np.random.default_rng(seed)
        reads = []
        active = list(range(n_seqs))       # serving-like row slots
        seq_len = dict.fromkeys(active, 0)
        next_seq = n_seqs
        for step in range(steps):
            slot = step % n_seqs
            seq = active[slot]
            n = int(rng.integers(2, 6))
            if seq_len[seq] + n > 20:      # row finished: release, readmit
                kv.release(seq)
                seq = active[slot] = next_seq
                seq_len[seq] = 0
                next_seq += 1
            toks = rng.standard_normal(
                (kvspec.num_layers, 2, n, kvspec.kv_heads,
                 kvspec.head_dim)).astype(np.float32)
            kv.append(seq, toks)
            seq_len[seq] += n
            if async_tiering:
                kv.prefetch(sorted(kv.block_table))
            if step % 3 == 2:      # periodic gather faults spilled pages
                reads.append(np.asarray(
                    kv.read(seq, step % kvspec.num_layers)))
        kv.flush_transfers()
        return reads, dict(kv.stats), clock.now

    plan = FaultPlan(seed=seed, transfer_fail_rate=fault_rate,
                     transfer_delay_rate=fault_rate)
    ref_reads, s, t_sync = kv_chaos(False, None)
    chaos_reads, a, t_chaos = kv_chaos(True, plan)
    chaos = {
        "fault_rate": fault_rate,
        "reads_identical": all(np.array_equal(x, y) for x, y
                               in zip(ref_reads, chaos_reads)),
        "conservation": (a["prefetch_hits"] + a["pool_faults"]
                         + a["retried_faults"] == s["pool_faults"]),
        "sync_pool_faults": s["pool_faults"],
        "sim_time_s": t_chaos, "sync_sim_time_s": t_sync,
    }
    for key in ("transfer_failures", "transfer_retries", "retried_faults",
                "prefetch_hits", "pool_faults", "tiering_degraded"):
        chaos[key] = a[key]

    # ---- recovery leg: model-backed crash-at-tick-k sweep ---------------
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServeConfig, ServingEngine

    cfg = get_config(arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    n_req = 3 if smoke else 4
    prompt_lens = [int(x) for x in rng.choice((12, 20), n_req)]
    max_new = 12 if smoke else 24
    max_len = max(prompt_lens) + max_new + 1
    max_len += -max_len % 8
    page_tokens = 8
    mcfg = model.cfg
    group_bytes = (mcfg.num_layers * 2 * page_tokens
                   * max(mcfg.num_kv_heads, 1) * max(mcfg.head_dim, 1)
                   * np.dtype(model.compute_dtype).itemsize)
    tight = (-(-max_len // page_tokens) + 3) * group_bytes
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in prompt_lens]

    def mk_engine(journal=None, fault_plan=None):
        return ServingEngine(model, params, ServeConfig(
            max_len=max_len, page_tokens=page_tokens,
            engine_spec=EngineSpec(engine="paged", kv_hbm_bytes=tight,
                                   async_tiering=True),
            max_batch_seqs=4, speculate_k=2,
            journal=journal, fault_plan=fault_plan))

    def reqs():
        return [Request(rid=i, prompt=prompts[i].copy(), max_new=max_new)
                for i in range(n_req)]

    ref = reqs()
    mk_engine().generate(ref)
    want = [list(r.generated) for r in ref]

    sweep = []
    for crash_tick in ((2, 5) if smoke else (1, 3, 6, 10)):
        journal = ServingJournal()
        cplan = FaultPlan(seed=seed, transfer_fail_rate=fault_rate,
                          transfer_delay_rate=fault_rate,
                          crash_at_tick=crash_tick)
        eng, rs = mk_engine(journal, cplan), reqs()
        try:
            eng.generate(rs)
            crashed = False
        except CrashFault:
            crashed = True
        state, last_tick = journal.replay()
        durable = sum(len(t) for t in state.values())
        rec = mk_engine(journal)
        rec.recover(rs)
        sweep.append({
            "crash_tick": crash_tick, "crashed": crashed,
            "durable_tokens_at_crash": durable,
            "journal_tick_at_crash": last_tick,
            "token_identical": [list(r.generated) for r in rs] == want,
            "recovery_sim_time_s": rec.stats()["sim_time_s"],
            "degraded_ticks": eng.sched_stats.get(
                "sched_degraded_ticks", 0),
        })
    return {"chaos": chaos, "crash_sweep": sweep,
            "config": {"arch": arch, "fault_rate": fault_rate,
                       "chaos_steps": steps, "chaos_pool_pages": pool_pages,
                       "requests": n_req, "prompt_lens": prompt_lens,
                       "max_new": max_new, "smoke": smoke}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=512)
    ap.add_argument("--engines", default="all",
                    help="comma-separated KV engine names, or 'all' to "
                         "enumerate the registry")
    ap.add_argument("--workloads", default="decode",
                    help="comma-separated workload names "
                         "(decode/prefill/mixed/serve/prefill_heavy), or "
                         "'all'")
    ap.add_argument("--drain-shards", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized serve workload (seconds, still preempts)")
    ap.add_argument("--no-pool", dest="pool", action="store_false",
                    help="serve workloads: force pool-capable engines onto "
                         "the host-mirror path (baseline for the pooled "
                         "decode-throughput comparison)")
    ap.add_argument("--no-fuse", dest="fused_bench", action="store_false",
                    help="skip the model-backed fused-vs-unfused tick "
                         "comparison that normally accompanies serve-style "
                         "workloads")
    ap.add_argument("--fused-gate", action="store_true",
                    help="CI: exit nonzero unless the fused mixed-batch "
                         "tick beats the batch=1-per-chunk baseline")
    ap.add_argument("--prefix-gate", action="store_true",
                    help="CI: exit nonzero unless the shared_prefix "
                         "workload actually shared — prefix hit rate > 0 "
                         "and at least one boundary-page copy-on-write on "
                         "the pooled engine")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="run the model-backed draft-and-verify comparison "
                         "at this k (0 = skip) and commit 1 + a∈[0,k] "
                         "tokens per decode step in the serve-workload "
                         "twins")
    ap.add_argument("--spec-gate", action="store_true",
                    help="CI: exit nonzero unless speculation commits more "
                         "than one token per decode row-launch "
                         "(accepted-tokens-per-launch > 1.0) with tokens "
                         "identical to the non-speculative run")
    ap.add_argument("--families", default="",
                    help="run the model-backed per-family pooled-vs-mirror "
                         "comparison: 'all' or a comma list from "
                         "dense/mla/int8/moe/ssm (default: skip)")
    ap.add_argument("--family-gate", action="store_true",
                    help="CI: exit nonzero unless every descriptor family "
                         "runs pooled mirror-free and token-identical to "
                         "its mirror baseline, beats it >= 5x on simulated "
                         "decode throughput where the mirror moves bytes, "
                         "and int8 holds <= 0.55x the fp16 pool "
                         "bytes/token")
    ap.add_argument("--async-tiering", action="store_true",
                    help="run the sync-vs-async transfer-pipeline "
                         "comparison on a deliberately tight pool plus the "
                         "model-backed token-identity check")
    ap.add_argument("--tiering-gate", action="store_true",
                    help="CI: exit nonzero unless async tiering beats the "
                         "synchronous baseline on simulated throughput "
                         "with prefetch_hits > 0 and stall_ticks_saved > "
                         "0, stays token-identical, and satisfies the "
                         "fault-conservation invariant")
    ap.add_argument("--faults", action="store_true",
                    help="run the fault-tolerance benchmark: a seeded "
                         "chaos run (failed/delayed transfers at ~1e-2 "
                         "per attempt) on a tight pool plus the "
                         "model-backed crash-at-tick-k recovery sweep "
                         "through the NVMM token journal")
    ap.add_argument("--fault-rate", type=float, default=1e-2,
                    help="per-attempt transfer fail AND delay probability "
                         "for the chaos leg")
    ap.add_argument("--fault-gate", action="store_true",
                    help="CI: exit nonzero unless the chaos run stays "
                         "byte-identical with exact fault conservation and "
                         "nonzero injected retries, and every "
                         "crash-at-tick-k recovery is token-identical to "
                         "the uninterrupted run")
    ap.add_argument("--out", default="artifacts/kvcache_bench.json")
    ap.add_argument("--serve-out", default="BENCH_serve.json",
                    help="repo-root serving perf record (written whenever "
                         "a serve-style workload runs)")
    args = ap.parse_args(argv)
    engines = (list_kv_engines() if args.engines == "all"
               else tuple(args.engines.split(",")))
    wl_names = ([w.name for w in kv_workloads()] + list(serve_workloads())
                if args.workloads == "all" else args.workloads.split(","))
    rows = [bench(e, tokens=args.tokens, workload=w,
                  drain_shards=args.drain_shards, smoke=args.smoke,
                  pool=args.pool, speculate_k=args.speculate_k)
            for w in wl_names for e in engines]
    serve_rows = [r for r in rows if r["workload"] in serve_workloads()]
    fused = None
    if serve_rows and args.fused_bench:
        fused = bench_fused_ticks(smoke=args.smoke)
    spec = None
    if args.speculate_k > 0:
        spec = bench_speculative(smoke=args.smoke, k=args.speculate_k)
    tiering = None
    if args.async_tiering:
        tiering = bench_async_tiering(smoke=args.smoke)
    fam_rows = None
    if args.families:
        fam_rows = bench_families(smoke=args.smoke, families=args.families)
    faults = None
    if args.faults:
        faults = bench_faults(smoke=args.smoke, fault_rate=args.fault_rate)
    print("design,workload,sim_time_s,write_amp,host_read_MB,"
          "tput_tok_s,p50_ms,p99_ms,preempts,pool_hit,d2h_saved_MB")
    for r in rows:
        hit = r.get("pool_hit_rate")
        serve_cols = (f"{r['throughput_tok_per_s']:.0f},"
                      f"{r['p50_latency_s']*1e3:.2f},"
                      f"{r['p99_latency_s']*1e3:.2f},"
                      f"{r['preempts']},"
                      f"{'' if hit is None else f'{hit:.3f}'},"
                      f"{r['mirror_d2h_saved_bytes']/1e6:.1f}"
                      if r["workload"] in serve_workloads() else ",,,,,")
        name = r["design"] + ("+pool" if r["pooled"] else "")
        print(f"{name},{r['workload']},{r['sim_time_s']:.4f},"
              f"{r['write_amplification']:.2f},"
              f"{r['host_read_bytes']/1e6:.1f},{serve_cols}")
    if fused is not None:
        print(f"fused-vs-unfused ticks: "
              f"{fused['fused']['tokens_per_s']:.1f} vs "
              f"{fused['unfused']['tokens_per_s']:.1f} tok/s "
              f"(x{fused['speedup_wall']:.2f} wall), "
              f"{fused['fused']['step_calls']} vs "
              f"{fused['unfused']['step_calls']} launches "
              f"(x{fused['launch_ratio']:.2f})")
    if spec is not None:
        sp = spec["speculative"]
        print(f"speculative k={sp['speculate_k']}: "
              f"{sp['accepted_tokens_per_launch']:.2f} accepted tokens "
              f"per decode launch "
              f"(acceptance {sp['acceptance_rate']:.2f}, "
              f"{sp['step_calls']} vs "
              f"{spec['baseline']['step_calls']} launches, "
              f"x{spec['speedup_wall']:.2f} wall, "
              f"token-identical={spec['token_identical']})")
    if fam_rows is not None:
        for r in fam_rows:
            ratio = r["decode_tput_sim_ratio"]
            print(f"family={r['family']:5s} "
                  f"planes={','.join(r['planes']) or '-':24s} "
                  f"pooled={r['pooled']['pooled']} "
                  f"mirror_d2h_bytes={r['pooled']['mirror_d2h_bytes']} "
                  f"saved={r['mirror_d2h_saved_bytes']} "
                  f"sim_tput_ratio="
                  f"{'n/a' if ratio is None else f'{ratio:.1f}x'} "
                  f"token-identical={r['token_identical']}")
    if tiering is not None:
        ts, ta = tiering["sync"], tiering["async"]
        tm = tiering["model"]
        print(f"async tiering: {ta['throughput_tok_per_s']:.0f} vs "
              f"{ts['throughput_tok_per_s']:.0f} tok/s sim "
              f"(x{tiering['speedup_sim']:.2f}, "
              f"{tiering['stall_s_removed']*1e3:.2f} ms of stalls "
              f"removed), {ta['prefetch_hits']} prefetch hits / "
              f"{ta['async_spills']} async spills / "
              f"{ta['stall_ticks_saved']} stalls saved, "
              f"token-identical={tm['token_identical']}, "
              f"fault-conservation={tm['fault_conservation']}")
    if faults is not None:
        fc, sw = faults["chaos"], faults["crash_sweep"]
        n_ok = sum(1 for e in sw if e["token_identical"])
        print(f"faults: chaos rate={fc['fault_rate']:g} injected "
              f"{fc['transfer_failures']} failures / "
              f"{fc['transfer_retries']} retries, "
              f"reads-identical={fc['reads_identical']}, "
              f"conservation={fc['conservation']}; crash sweep "
              f"{n_ok}/{len(sw)} recoveries token-identical "
              f"(crashed at ticks "
              f"{[e['crash_tick'] for e in sw if e['crashed']]})")
    # write the artifacts BEFORE the gates so a failing CI run still leaves
    # the evidence of what regressed
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rows, indent=1))
    if (serve_rows or spec is not None or tiering is not None
            or fam_rows is not None or faults is not None):
        # merge into the existing record so separate CI steps (the
        # serve/prefill_heavy smoke, the shared_prefix smoke, the
        # speculative smoke) compose instead of clobbering each other:
        # this run's rows replace entries with the same (design,
        # workload); a prior fused/speculative comparison is kept when
        # this run skipped it
        serve_path = Path(args.serve_out)
        prior = {}
        if serve_path.exists():
            try:
                prior = json.loads(serve_path.read_text())
            except (ValueError, OSError):
                prior = {}
        fresh = {(r["design"], r["workload"]) for r in serve_rows}
        keep = [r for r in prior.get("engines", [])
                if (r.get("design"), r.get("workload")) not in fresh]
        fresh_fam = {(r["design"], r["workload"], r["family"])
                     for r in (fam_rows or [])}
        keep_fam = [r for r in prior.get("families", [])
                    if (r.get("design"), r.get("workload"),
                        r.get("family")) not in fresh_fam]
        serve_path.write_text(json.dumps(
            {"engines": keep + serve_rows,
             "families": keep_fam + (fam_rows or []),
             "fused_vs_unfused": (prior.get("fused_vs_unfused")
                                  if fused is None else fused),
             "speculative": (prior.get("speculative")
                             if spec is None else spec),
             "tiering": (prior.get("tiering")
                         if tiering is None else tiering),
             "faults": (prior.get("faults")
                        if faults is None else faults)},
            indent=1, sort_keys=True))
    if any(r["workload"] in serve_workloads() and not r["preempts"]
           for r in rows):
        raise SystemExit("serve workload never crossed the HBM budget — "
                         "preemption path not exercised")
    if args.prefix_gate:
        shared = [r for r in rows
                  if r["workload"] == "shared_prefix" and r["pooled"]]
        if not shared:
            raise SystemExit("--prefix-gate needs the shared_prefix "
                             "workload on a pool-capable engine")
        for r in shared:
            if not r.get("prefix_hit_rate"):
                raise SystemExit(
                    f"prefix cache never hit on {r['design']} "
                    f"(hit rate {r.get('prefix_hit_rate')}) — the sharing "
                    f"path is dead")
            if not r.get("cow_copies"):
                raise SystemExit(
                    f"boundary-page copy-on-write never fired on "
                    f"{r['design']} — divergence over shared pages is not "
                    f"being exercised")
    if args.fused_gate:
        if fused is None:
            raise SystemExit("--fused-gate needs a serve-style workload "
                             "and the fused bench enabled")
        # gate on the DETERMINISTIC structural property (model launches per
        # schedule — one fused forward per tick must beat the
        # batch=1-per-chunk launch count), not on wall clock, which a
        # noisy CI runner could flip without any code regression; the wall
        # speedup is still recorded in BENCH_serve.json and warned about
        if fused["launch_ratio"] <= 1.0:
            raise SystemExit(
                f"fused mixed-batch ticks do NOT launch fewer model steps "
                f"than the batch=1-per-chunk baseline "
                f"(x{fused['launch_ratio']:.2f}) — the regression this "
                f"gate exists to prevent")
        if fused["speedup_wall"] <= 1.0:
            print(f"WARNING: fused wall speedup x"
                  f"{fused['speedup_wall']:.2f} <= 1 on this runner "
                  f"(launch ratio x{fused['launch_ratio']:.2f} still "
                  f"holds)")
    if args.spec_gate:
        if spec is None:
            raise SystemExit("--spec-gate needs --speculate-k > 0")
        # correctness first: speculation is only legal because it is exact
        if not spec["token_identical"]:
            raise SystemExit(
                "speculative run produced DIFFERENT tokens than the "
                "non-speculative run — draft-and-verify is no longer exact")
        # then the DETERMINISTIC structural win (committed decode tokens
        # per decode row-launch), not wall clock — same reasoning as
        # --fused-gate: a noisy runner must not flip the verdict
        atpl = spec["speculative"]["accepted_tokens_per_launch"]
        if atpl <= 1.0:
            raise SystemExit(
                f"speculation commits {atpl:.2f} tokens per decode "
                f"row-launch (<= 1.0): no draft ever survived "
                f"verification — the win this gate exists to prevent "
                f"regressing")
        if spec["speedup_wall"] <= 1.0:
            print(f"WARNING: speculative wall speedup x"
                  f"{spec['speedup_wall']:.2f} <= 1 on this runner "
                  f"({atpl:.2f} accepted tokens per launch still holds)")
    if args.family_gate:
        if fam_rows is None:
            raise SystemExit("--family-gate needs --families")
        for r in fam_rows:
            fam = r["family"]
            # correctness first, same order as the other gates: the
            # descriptor layouts are only legal because they are exact
            if not r["token_identical"]:
                raise SystemExit(
                    f"family {fam}: pooled run produced DIFFERENT tokens "
                    f"than the mirror baseline — the descriptor layout is "
                    f"no longer exact")
            if not r["pooled"]["pooled"] or not r["pooled"]["fused"]:
                raise SystemExit(
                    f"family {fam}: fell off the pooled fused path "
                    f"(pooled={r['pooled']['pooled']}, "
                    f"fused={r['pooled']['fused']}) — the mirror fallback "
                    f"is silently eating the family")
            if r["pooled"]["mirror_d2h_bytes"] != 0:
                raise SystemExit(
                    f"family {fam}: pooled path mirrored "
                    f"{r['pooled']['mirror_d2h_bytes']} bytes device→host "
                    f"— the zero-mirror invariant broke")
            ratio = r["decode_tput_sim_ratio"]
            if ratio is not None and ratio < 5.0:
                raise SystemExit(
                    f"family {fam}: pooled simulated decode throughput is "
                    f"only x{ratio:.2f} the mirror baseline (< 5x) — the "
                    f"win this gate exists to prevent regressing")
            if fam == "int8" and r["bytes_per_token_vs_fp16"] > 0.55:
                raise SystemExit(
                    f"int8 pool holds "
                    f"{r['bytes_per_token_vs_fp16']:.3f}x the fp16 "
                    f"bytes/token (> 0.55x) — the scale planes outgrew "
                    f"the quantization win")
    if args.tiering_gate:
        if tiering is None:
            raise SystemExit("--tiering-gate needs --async-tiering")
        ts, ta = tiering["sync"], tiering["async"]
        tm = tiering["model"]
        # correctness first, same order as --spec-gate: the pipeline is
        # only legal because it is timing-only
        if not tm["token_identical"]:
            raise SystemExit(
                "async tiering produced DIFFERENT tokens than the "
                "synchronous run — the pipeline is no longer timing-only")
        if not tm["fault_conservation"]:
            raise SystemExit(
                f"fault conservation broken: async prefetch_hits "
                f"({tm['async']['prefetch_hits']}) + pool_faults "
                f"({tm['async']['pool_faults']}) != sync pool_faults "
                f"({tm['sync']['pool_faults']}) — prefetch is changing "
                f"allocation decisions")
        if not ts["pool_faults"]:
            raise SystemExit(
                "tiering twin never faulted a page — the tight-pool "
                "regime this gate measures is dead")
        # then the win, on SIMULATED throughput — deterministic on any
        # runner, unlike wall clock (same reasoning as the other gates)
        if ta["throughput_tok_per_s"] <= ts["throughput_tok_per_s"]:
            raise SystemExit(
                f"async tiering does NOT beat the synchronous baseline "
                f"({ta['throughput_tok_per_s']:.0f} vs "
                f"{ts['throughput_tok_per_s']:.0f} tok/s sim) — the "
                f"regression this gate exists to prevent")
        if not ta["prefetch_hits"] or not ta["stall_ticks_saved"]:
            raise SystemExit(
                f"async pipeline is idle: prefetch_hits="
                f"{ta['prefetch_hits']}, stall_ticks_saved="
                f"{ta['stall_ticks_saved']} — transfers are not actually "
                f"overlapping the forward")
    if args.fault_gate:
        if faults is None:
            raise SystemExit("--fault-gate needs --faults")
        fc = faults["chaos"]
        # correctness first, same order as the other gates: faults are
        # only survivable because retry/degradation is exact
        if not fc["reads_identical"]:
            raise SystemExit(
                "chaos run returned DIFFERENT bytes than the fault-free "
                "run — transfer faults are no longer timing-only")
        if not fc["conservation"]:
            raise SystemExit(
                f"fault conservation broken under chaos: prefetch_hits "
                f"({fc['prefetch_hits']}) + pool_faults "
                f"({fc['pool_faults']}) + retried_faults "
                f"({fc['retried_faults']}) != fault-free pool_faults "
                f"({fc['sync_pool_faults']})")
        # the gate is vacuous unless faults actually fired and were
        # retried — a silent injector must fail CI, not pass it
        if not fc["transfer_failures"] or not fc["transfer_retries"]:
            raise SystemExit(
                f"chaos leg injected no retried faults "
                f"(failures={fc['transfer_failures']}, "
                f"retries={fc['transfer_retries']}) — the injector or the "
                f"retry path is dead")
        for e in faults["crash_sweep"]:
            if not e["token_identical"]:
                raise SystemExit(
                    f"recovery after crash at tick {e['crash_tick']} "
                    f"produced DIFFERENT tokens than the uninterrupted "
                    f"run — the journal/recovery path lost or reordered "
                    f"committed tokens")
        if not any(e["crashed"] for e in faults["crash_sweep"]):
            raise SystemExit(
                "crash sweep never actually crashed — every crash tick "
                "fell past the run's end, the recovery path went "
                "unexercised")
    return rows


if __name__ == "__main__":
    main()
