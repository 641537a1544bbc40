"""Serving driver: continuous-batching decode through the tiered-KV engine,
comparing the paper's designs at the KV call-site under real concurrency.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b-smoke \
        --design log --requests 4 --max-new 16 --max-batch-seqs 4

Requests share one running batch (admitted/preempted/restored by the
scheduler); ``--hbm-budget-bytes`` small enough to bind makes the
preemption path visible in the printed stats. ``--sequential`` runs the
one-at-a-time reference loop instead (same tokens, no batching).

``--paged-decode`` forces the mirror-free pooled path (decode runs the
paged_attention kernel directly over the engine's device page pool;
``mirror_d2h_bytes`` stays 0); ``--mirror-decode`` forces the dense-mirror
path; default is auto (pooled when engine + model support it).
``--prefill-chunk-tokens`` splits long prompts across scheduler ticks.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core.engines import EngineSpec, list_kv_engines
from repro.launch.compile_cache import enable_compilation_cache
from repro.models import build_model
from repro.serving import Request, ServeConfig, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b-smoke")
    ap.add_argument("--design", "--engine", dest="design",
                    choices=list_kv_engines(), default="log",
                    help="KV engine from the registry")
    ap.add_argument("--drain-shards", type=int, default=1,
                    help="per-shard drainer parallelism (log/kvhybrid)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch-seqs", type=int, default=8,
                    help="continuous-batching width cap")
    ap.add_argument("--max-batch-tokens", type=int, default=None,
                    help="running-batch token cap (None = unlimited)")
    ap.add_argument("--hbm-budget-bytes", type=int, default=64 << 20,
                    help="KV-tier HBM budget; small values force "
                         "preempt/restore cycles")
    ap.add_argument("--sequential", action="store_true",
                    help="run the batch=1 reference loop instead of the "
                         "continuous-batching scheduler")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--paged-decode", dest="paged_decode",
                      action="store_true", default=None,
                      help="force mirror-free decode over the device page "
                           "pool (requires a pool-capable engine)")
    mode.add_argument("--mirror-decode", dest="paged_decode",
                      action="store_false",
                      help="force the dense-mirror decode path")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="tokens per KV page (pool geometry)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="split prompts longer than this across ticks "
                         "(default: max-batch-tokens)")
    ap.add_argument("--no-fuse", dest="fuse_ticks", action="store_false",
                    help="disable fused mixed-batch ticks: prefill chunks "
                         "run at batch=1 through the decode path (the "
                         "pre-fusion baseline)")
    ap.add_argument("--prefix-cache-tokens", type=int, default=0,
                    help="cross-request prefix cache capacity in tokens "
                         "(0 = off): cache-hit admissions splice shared "
                         "pool pages instead of prefilling (pooled path "
                         "only)")
    ap.add_argument("--shared-prefix-tokens", type=int, default=0,
                    help="prepend this many identical tokens to every "
                         "prompt (exercises the prefix cache)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="speculative decode: up to k self-drafted tokens "
                         "per decode row per fused tick, verified in the "
                         "same launch (0 = off; tokens are identical "
                         "either way)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for deterministic fault injection (the same "
                         "seed replays the same faults)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="per-attempt transfer fail AND delay probability "
                         "(>0 turns on the injector; retries/degradation "
                         "show up in the printed stats)")
    ap.add_argument("--crash-at-tick", type=int, default=None,
                    help="inject a CrashFault at this scheduler tick; with "
                         "--journal the run then recovers from the journal "
                         "and prints both halves")
    ap.add_argument("--journal", action="store_true",
                    help="append committed tokens to a crash-consistent "
                         "NVMM journal every tick (required for recovery "
                         "after --crash-at-tick)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compilation_cache()
    cfg = get_config(args.arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(args.seed))
    prompt_len = args.prompt_len + args.shared_prefix_tokens
    max_len = prompt_len + args.max_new + 1
    max_len += -max_len % args.page_tokens     # pool wants page alignment

    journal = None
    if args.journal:
        from repro.serving.journal import ServingJournal
        journal = ServingJournal()
    fault_plan = None
    if args.fault_rate > 0.0 or args.crash_at_tick is not None:
        from repro.serving.faults import FaultPlan
        fault_plan = FaultPlan(seed=args.fault_seed,
                               transfer_fail_rate=args.fault_rate,
                               transfer_delay_rate=args.fault_rate,
                               crash_at_tick=args.crash_at_tick)

    def mk_engine(plan):
        return ServingEngine(model, params, ServeConfig(
            max_len=max_len, page_tokens=args.page_tokens,
            engine_spec=EngineSpec(
                engine=args.design,
                drain_shards=args.drain_shards,
                kv_hbm_bytes=args.hbm_budget_bytes,
                prefix_cache_tokens=args.prefix_cache_tokens),
            max_batch_seqs=args.max_batch_seqs,
            max_batch_tokens=args.max_batch_tokens,
            paged_decode=args.paged_decode,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
            fuse_ticks=args.fuse_ticks,
            speculate_k=args.speculate_k,
            journal=journal, fault_plan=plan))

    engine = mk_engine(fault_plan)

    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size, args.shared_prefix_tokens,
                          dtype=np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate([
                        shared,
                        rng.integers(0, cfg.vocab_size, args.prompt_len,
                                     dtype=np.int32)]),
                    max_new=args.max_new)
            for i in range(args.requests)]
    if args.sequential:
        engine.generate_sequential(reqs)
    else:
        try:
            engine.generate(reqs)
        except Exception as e:
            from repro.serving.faults import CrashFault
            if not isinstance(e, CrashFault):
                raise
            print(f"CRASH: {e} "
                  f"(journal stats: {engine.journal.stats if journal else None})")
            if journal is None:
                raise SystemExit(
                    "crashed without --journal: nothing durable to recover")
            # a fresh engine sharing the SAME journal resumes exactly where
            # the last durable tick stopped
            engine = mk_engine(None)
            engine.recover(reqs)
            print("RECOVERED: journal replayed, unfinished rows resumed")
    for r in reqs:
        print(f"req {r.rid}: generated {len(r.generated)} tokens "
              f"{r.generated[:8]}...")
    mode = ("sequential" if args.sequential else
            ("batched+pooled" if engine.pooled else "batched+mirror")
            + ("+fused" if engine.fused else ""))
    dev = jax.devices()[0]
    print(f"tiered-kv[{args.design}] ({mode}) on {dev.platform} "
          f"{dev.device_kind} x{len(jax.devices())} stats: {engine.stats()}")


if __name__ == "__main__":
    main()
