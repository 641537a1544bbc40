"""Drive one cell through the served path and record what happened.

The system under test is the program's ``ServingEngine`` (the ``paged``
engine: device page pool, fused ragged ticks, Pallas paged attention) under
its ``Scheduler``. The harness calls ``Scheduler.tick()`` itself; before
each tick it queues every request whose due time has passed, and when
nothing runs it sleeps until the next one is due. It never calls
``generate()``. Token times are taken on the host after each tick from
``len(req.generated)``: the tick's argmax has already synced.

``Scheduler.tick``, ``ServingEngine.step_batch`` and
``ServingEngine.prefill_one`` are wrapped on the instances in
``jax.profiler.TraceAnnotation`` spans (``bench.tick``,
``bench.step_batch``, ``bench.prefill_one``); the wrappers also record each
fused step's rows as ``(ctx_len, q_len)`` and each admission's length.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import traffic

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclass
class Served:
    """One request as the window saw it (times in seconds after the
    window opened; ``due`` None for a backlog request)."""
    rid: int
    due: float | None
    admitted: float | None = None
    token_times: list = field(default_factory=list)


@dataclass
class Record:
    """What a run recorded: requests, ticks, fused steps, admissions and the
    engine's counters, all on the host clock relative to window open."""
    seconds: float
    served: dict = field(default_factory=dict)
    ticks: list = field(default_factory=list)        # (start, end)
    steps: list = field(default_factory=list)        # [(ctx, q), ...]
    prefills: list = field(default_factory=list)     # (rid, n, start, end)
    lateness: list = field(default_factory=list)     # seconds per request
    close: float = 0.0
    counters_open: dict = field(default_factory=dict)
    counters_close: dict = field(default_factory=dict)
    compiles_in_window: int = 0
    cache_loads_in_window: int = 0
    preempted: set = field(default_factory=set)   # rids spilled at any time


def new_record(planned: list, seconds: float) -> Record:
    rec = Record(seconds=seconds)
    for p in planned:
        rec.served[p.rid] = Served(rid=p.rid, due=p.due)
    return rec


class CompileCounter:
    """Counts the programs JAX compiles and the persistent-cache hits among
    them, through ``jax.monitoring``."""

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def build(cfg: dict, seed: int, program_cfg=None):
    """The program's model at the configuration's published widths, with
    the benchmark's weights from ``seed``. ``program_cfg`` overrides the
    program's registry entry (tests run a reduced one). The registry entry
    has to hold every field the configuration's family states: a float to
    a relative 1e-9, anything else exactly."""
    from repro.configs import get_config
    from repro.models import build_model

    import families
    import weights

    pc = program_cfg or get_config(cfg["program_arch"])
    differ = {}
    for name, want in families.load(cfg).program_fields(cfg).items():
        have = program_field(pc, name)
        if isinstance(want, float) and isinstance(have, (int, float)):
            same = abs(have - want) <= 1e-9 * abs(want)
        else:
            same = have == want
        if not same:
            differ[name] = (have, want)
    if differ:
        raise RuntimeError(f"the program's {pc.name} differs from "
                           f"{cfg['name']}: {differ}")
    dt = DTYPES[cfg["param_dtype"]]
    model = build_model(pc, param_dtype=dt, compute_dtype=dt, remat=False)
    weights.check_against(jax.eval_shape(model.init, jax.random.key(0)),
                          cfg)
    return model, weights.make_params(cfg, seed)


def program_field(pc, name: str):
    """Attribute ``name`` of the program's config, dotted through its
    sub-configs (``moe.num_experts``); None where a step is missing."""
    for part in name.split("."):
        pc = getattr(pc, part, None)
    return pc


def make_engine(model, params, cfg: dict):
    from repro.core.engines import EngineSpec
    from repro.serving import ServeConfig, ServingEngine
    from repro.serving.scheduler import Scheduler

    engine = ServingEngine(model, params, ServeConfig(
        max_len=cfg["max_len"], page_tokens=cfg["page_tokens"],
        engine_spec=EngineSpec(engine="paged",
                               kv_hbm_bytes=cfg["kv_pool_bytes"]),
        max_batch_seqs=cfg["max_batch_seqs"], paged_decode=True,
        fuse_ticks=True, prefill_chunk_tokens=cfg["prefill_chunk_tokens"]))
    if not (engine.pooled and engine.fused):
        raise RuntimeError(f"engine is not pooled and fused: pooled="
                           f"{engine.pooled} fused={engine.fused}")
    return engine, Scheduler(engine, [])


def instrument(engine, rec: Record, clock) -> None:
    """Wrap the engine's step and admission on the instance, in named
    spans, recording rows and admissions."""
    step, prefill = engine.step_batch, engine.prefill_one
    seq_len = engine.tiered.seq_len

    def step_batch(rids, caches, tok_rows, *args, **kwargs):
        rows = [(int(seq_len.get(r, 0)), len(t))
                for r, t in zip(rids, tok_rows)]
        with jax.profiler.TraceAnnotation("bench.step_batch"):
            out = step(rids, caches, tok_rows, *args, **kwargs)
        rec.steps.append(rows)
        return out

    def prefill_one(req, n=None, tokens=None):
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.prefill_one"):
            out = prefill(req, n, tokens)
        rec.prefills.append((req.rid, len(req.prompt) if n is None else n,
                             t0, clock()))
        s = rec.served.get(req.rid)
        if s is not None and s.admitted is None:
            s.admitted = t0
        return out

    engine.step_batch = step_batch
    engine.prefill_one = prefill_one


def warm_up(engine, planned: list, cfg: dict) -> None:
    """Compile every program the run's requests can reach before the
    window: each admission prefill length (through ``prefill_one`` on a
    throwaway row), the fused step at every (rows, Qmax) bucket, and the
    small programs the scheduler and engine run on the step's outputs for
    every row length."""
    from repro.serving import Request

    chunk = cfg["prefill_chunk_tokens"]
    for i, n in enumerate(sorted(traffic.first_chunks(planned, chunk))):
        rid = -1 - i
        logits, _ = engine.prefill_one(
            Request(rid=rid, prompt=np.zeros(n, np.int32), max_new=1), n)
        int(jnp.argmax(logits[:, -1], -1)[0])
        engine.tiered.release(rid)
    lens = sorted(traffic.chunk_lengths(planned, chunk))
    qbs = sorted({traffic.pow2(c) for c in lens})
    bbs = sorted({traffic.pow2(b)
                  for b in range(1, cfg["max_batch_seqs"] + 1)})
    names = [p.name for p in engine.desc.paged_planes]
    for bb in bbs:
        for qb in qbs:
            cache = {"block_table": jnp.asarray(
                np.zeros((bb, engine.max_pages), np.int32))}
            for n, v in zip(names, engine.tiered.pool_views()):
                cache["pool_" + n] = v
            zeros = np.zeros(bb, np.int32)
            logits, out = engine._step_paged_ragged(
                engine.params, cache, jnp.asarray(np.zeros((bb, qb),
                                                           np.int32)),
                jnp.asarray(zeros), jnp.asarray(zeros))
            out["pos"][0:1].block_until_ready()
            for c in lens:
                if traffic.pow2(c) <= qb:
                    row = logits[0:1, :c]
                    int(jnp.argmax(row[:, -1], -1)[0])
            del logits, out
    warm_preemption(engine, max(traffic.first_chunks(planned, chunk)),
                    cfg["page_tokens"])
    jax.block_until_ready(engine.tiered.pool_views())


def warm_preemption(engine, n: int, page_tokens: int) -> None:
    """A tight pool preempts rows: the scheduler spills a row's pages to the
    host and restores them page by page, the last page holding
    ``length % page_tokens`` tokens, in small programs of their own. Run a
    throwaway row of ``n`` prompt tokens through the scheduler's own
    preemption and restore once for every such remainder, then finish it."""
    from repro.serving import Request
    from repro.serving.scheduler import Scheduler

    sched = Scheduler(engine, [])
    req = Request(rid=-1000, prompt=np.zeros(n, np.int32),
                  max_new=page_tokens + 3)
    sched.waiting.append(req)
    for _ in range(page_tokens + 1):
        sched.tick()
        sched._preempt_one()
    while sched.tick():
        pass


def fill_backlog(sched, planned: list) -> dict:
    """Queue every request, then tick until the running set has filled and
    its first ``max_batch_seqs`` rows have all finished. Returns the live
    Request objects by rid."""
    from repro.serving import Request

    reqs = {}
    for p in planned:
        req = Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
        reqs[p.rid] = req
        sched.waiting.append(req)
    first = [r.rid for r in planned[:sched.max_batch_seqs]]
    while not all(reqs[r].done for r in first):
        if not sched.tick():
            raise RuntimeError("the backlog drained before the window")
    return reqs


def run_window(sched, rec: Record, clock, compiles: CompileCounter,
               reqs: dict, planned: list = ()) -> dict:
    """Tick for ``rec.seconds``. Open loop: ``planned`` requests are queued
    as they fall due, and the harness sleeps when nothing runs. Backlog:
    ``reqs`` were queued before, and the queue must not drain. Returns the
    live Request objects by rid."""
    from repro.serving import Request

    pending = list(planned)
    for rid, req in reqs.items():
        rec.served[rid].token_times = [-1.0] * len(req.generated)
    n0, h0 = compiles.programs, compiles.cache_hits
    with jax.profiler.TraceAnnotation("bench.window"):
        clock.open()
        rec.steps.clear()
        rec.prefills.clear()
        while (now := clock()) < rec.seconds:
            while pending and pending[0].due <= now:
                p = pending.pop(0)
                req = Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
                reqs[p.rid] = req
                rec.lateness.append(now - p.due)
                sched.waiting.append(req)
            if sched.running or sched.waiting or sched.preempted:
                t0 = clock()
                with jax.profiler.TraceAnnotation("bench.tick"):
                    sched.tick()
                t1 = clock()
                rec.ticks.append((t0, t1))
                rec.preempted.update(p.req.rid for p in sched.preempted)
                for rid, req in reqs.items():
                    times = rec.served[rid].token_times
                    times.extend([t1] * (len(req.generated) - len(times)))
                if not planned and not sched.waiting:
                    raise RuntimeError("the backlog drained inside the "
                                       "window: the mix needs more requests")
            else:
                due = pending[0].due if pending else rec.seconds
                time.sleep(max(min(due, rec.seconds) - clock(), 0.0))
        jax.block_until_ready(sched.engine.tiered.pool_views())
        rec.close = clock()
    rec.compiles_in_window = compiles.programs - n0
    rec.cache_loads_in_window = compiles.cache_hits - h0
    return reqs


class Clock:
    """Seconds since the window opened (since construction until then)."""

    def __init__(self):
        self.zero = time.perf_counter()

    def open(self) -> float:
        self.zero = time.perf_counter()
        return self.zero

    def __call__(self) -> float:
        return time.perf_counter() - self.zero


def finish_for_check(sched, reqs: dict, want_tokens: int,
                     limit_s: float = 60.0) -> None:
    """After the window: tick on, with no new arrivals, until the finished
    requests hold ``want_tokens`` served tokens or ``limit_s`` passes."""
    t0 = time.perf_counter()
    while sum(len(r.generated) for r in reqs.values() if r.done) \
            < want_tokens and time.perf_counter() - t0 < limit_s:
        sched.waiting.clear()
        if not sched.tick():
            break
