"""The process tracer (``serving/trace.py``) and the spans and counters the
served tick records with it.

* the tracer alone: nesting and parent ids, the ring's bound and
  ``dropped``, per-name totals, a ``serve.compile`` span for a fresh jit,
  and the clock — under a CPU profile, the spans' ``TraceAnnotation``s sit
  at one constant offset from the ring's starts;
* the served tick at a reduced size: spans nest as the scheduler and
  engine open them, ``serve.tick.n`` counts the ticks run, and
  ``host_syncs``, ``rows.*``, ``slots.*`` and ``attn.blocks.*`` equal what
  the run's steps and its one preemption give when counted by hand;
* the fused step's ``jax.named_scope``s reach the compiled ops' metadata.
"""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engines import EngineSpec
from repro.kernels.paged_attention.kernel import ragged_grid_blocks
from repro.models import build_model
from repro.serving import Request, Scheduler, ServeConfig, ServingEngine
from repro.serving.trace import TRACER, Tracer

ARCH = "internlm2-1.8b-smoke"
PAGE = 4
CHUNK = 5


# ------------------------------------------------------------ the tracer
def test_spans_nest_with_parent_ids():
    t = Tracer()
    with t.span("a") as a:
        with t.span("b", rid=7) as b:
            with t.span("c") as c:
                pass
        with t.span("d") as d:
            pass
    with t.span("e") as e:
        pass
    got = {s.name: s for s in t.spans()}
    assert [s.name for s in t.spans()] == ["c", "b", "d", "a", "e"]
    assert got["a"].parent == 0 and got["e"].parent == 0
    assert got["b"].parent == a.id and got["d"].parent == a.id
    assert got["c"].parent == b.id
    assert got["b"].tag == 7 and got["a"].tag is None
    assert len({a.id, b.id, c.id, d.id, e.id}) == 5
    for s in t.spans():
        assert s.start_ns <= s.end_ns
        if s.parent:
            p = next(x for x in t.spans() if x.id == s.parent)
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
    t.record("q", 5, 9, 3, parent=0)
    assert t.spans()[-1][1:] == (0, "q", 5, 9, 3)
    assert t.counters()["spans"] == 6


def test_ring_is_bounded_and_counts_what_it_drops():
    t = Tracer(capacity=4)
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert [s.name for s in t.spans()] == ["s6", "s7", "s8", "s9"]
    c = t.counters()
    assert c["dropped"] == 6 and c["spans"] == 10
    t.reset()
    assert t.spans() == [] and t.counters()["dropped"] == 0


def test_per_name_totals_and_counters():
    t = Tracer(capacity=2)
    t.record("x", 100, 150)
    t.record("x", 200, 230)
    t.record("y", 0, 5)
    t.count("host_syncs")
    t.count("host_syncs", 4)
    c = t.counters()
    assert c["x.n"] == 2 and c["x.ns"] == 80
    assert c["y.n"] == 1 and c["y.ns"] == 5
    assert c["host_syncs"] == 5
    assert c["dropped"] == 1              # totals outlive the ring


def test_a_fresh_jit_is_a_compile_span_under_the_open_span():
    def traced_probe(x):
        return x * 3 + 1

    with TRACER.span("outer") as outer:
        jax.jit(traced_probe)(jnp.arange(5.0)).block_until_ready()
    c = TRACER.counters()
    assert c["compiles.jit(traced_probe)"] == 1
    assert c["compiles"] >= 1 and c["compile_ns"] > 0
    probe = [s for s in TRACER.spans() if s.name == "serve.compile"
             and s.tag == "jit(traced_probe)"]
    assert len(probe) == 1
    (s,) = probe
    out = next(x for x in TRACER.spans() if x.id == outer.id)
    assert s.parent == outer.id
    assert out.start_ns <= s.start_ns <= s.end_ns <= out.end_ns


def test_spans_sit_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(6):
            with TRACER.span("serve.tick"):
                with TRACER.span("serve.plan"):
                    time.sleep(0.001 * (i % 3))
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    traced = sorted((ev.start_ns, ev.name)
                    for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith("serve."))
    ring = sorted((s.start_ns, s.name) for s in TRACER.spans())
    assert [n for _, n in traced] == [n for _, n in ring]
    assert len(ring) == 12
    offsets = [a - b for (a, _), (b, _) in zip(traced, ring)]
    assert max(offsets) - min(offsets) < 100_000     # ns


# ------------------------------------------------------- the served tick
@pytest.fixture(scope="module")
def lm():
    cfg = get_config(ARCH)
    model = build_model(cfg, remat=False)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(lm):
    _, model, params = lm
    return ServingEngine(model, params, ServeConfig(
        max_len=48, page_tokens=PAGE,
        engine_spec=EngineSpec(engine="paged", kv_hbm_bytes=64 << 20),
        max_batch_seqs=4, paged_decode=True, fuse_ticks=True,
        prefill_chunk_tokens=CHUNK))


def _pow2(n):
    return 1 << (n - 1).bit_length()


def _served_run(lm):
    """Three requests (prompts 8, 12, 8; chunks of 5) through the fused
    pooled path, with one row preempted under pressure after the third
    tick and restored the tick after. Returns what a hand count needs."""
    cfg, _, _ = lm
    eng = _engine(lm)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32), max_new=5)
            for i, n in enumerate((8, 12, 8))]
    steps, ctxs = [], []
    step = eng.step_batch

    def recording(rids, caches, tok_rows, *a, **kw):
        steps.append([len(t) for t in tok_rows])
        ctxs.append([int(np.asarray(c["pos"])[0]) for c in caches])
        return step(rids, caches, tok_rows, *a, **kw)

    eng.step_batch = recording
    sched = Scheduler(eng, reqs)
    pressure = []
    sched._over_budget = lambda: bool(pressure and pressure.pop())
    before = TRACER.counters()
    ticks, victim_pages = 0, None
    while True:
        pressure.append(ticks == 2)
        more = sched.tick()
        ticks += 1
        if ticks == 3:
            (pre,) = sched.preempted
            victim_pages = -(-pre.length // PAGE)
        if not more:
            break
    after = TRACER.counters()
    delta = {k: v - before.get(k, 0) for k, v in after.items()}
    assert all(r.done for r in reqs)
    return eng, sched, reqs, steps, ctxs, ticks, victim_pages, delta


PARENT = {"serve.admit": "serve.tick", "serve.restore": "serve.tick",
          "serve.plan": "serve.tick", "serve.step": "serve.tick",
          "serve.retire": "serve.tick", "serve.publish": "serve.tick",
          "serve.prepare": "serve.step", "serve.launch": "serve.step",
          "serve.commit": "serve.step", "serve.rows": "serve.step",
          "serve.preempt": "serve.retire"}


def test_served_tick_spans_nest_as_the_layers_open_them(lm):
    _, sched, reqs, _, _, ticks, _, delta = _served_run(lm)
    spans = TRACER.spans()
    by_id = {s.id: s for s in spans}
    names = {s.name for s in spans}
    assert set(PARENT) | {"serve.tick", "serve.queue"} <= names
    for s in spans:
        if s.name in ("serve.tick", "serve.queue"):
            assert s.parent == 0, s
        elif s.name in PARENT:
            p = by_id[s.parent]
            assert p.name == PARENT[s.name], (s, p)
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
    assert delta["serve.tick.n"] == ticks == sched.stats.ticks
    queued = sorted(s.tag for s in spans if s.name == "serve.queue")
    assert queued == [r.rid for r in reqs]
    for r in reqs:
        (q,) = [s for s in spans if s.name == "serve.queue" and s.tag == r.rid]
        (a,) = [s for s in spans if s.name == "serve.admit" and s.tag == r.rid]
        assert q.start_ns == r.created_ns and q.end_ns <= a.start_ns
    assert delta["serve.preempt.n"] == delta["serve.restore.n"] == 1


def test_served_tick_counters_match_a_hand_count(lm):
    eng, _, _, steps, ctxs, _, victim_pages, delta = _served_run(lm)
    planes = len(eng.desc.paged_planes)
    decode = sum(q == 1 for qs in steps for q in qs)
    chunk = sum(q > 1 for qs in steps for q in qs)
    stepped = sum(len(qs) for qs in steps)
    assert decode and chunk and victim_pages
    # one argmax per planned decode row, one pos read per stepped row, and
    # the preemption's row (pos), logits and spilled pages (a read per
    # plane per page)
    assert delta["host_syncs"] == (decode + stepped + 2
                                   + planes * victim_pages)
    assert delta["rows.decode"] == decode and delta["rows.chunk"] == chunk
    assert delta["rows.pad"] == sum(_pow2(len(qs)) - len(qs) for qs in steps)
    all_slots = sum(_pow2(len(qs)) * _pow2(max(qs)) for qs in steps)
    assert delta["slots.all"] == all_slots
    assert delta["slots.pad"] == all_slots - sum(map(sum, steps))
    assert delta["serve.step.n"] == delta["serve.launch.n"] == len(steps)
    # the attention kernel's (query tile × KV block) pairs, held and
    # visited, by the kernel's own count over each step's padded rows
    cfg = lm[0]
    page_bytes = PAGE * sum(p.entry_bytes for p in eng.desc.paged_planes)
    every = live = 0
    for qs, cs in zip(steps, ctxs):
        pad = _pow2(len(qs)) - len(qs)
        q = np.asarray(qs + [0] * pad)
        a, v = ragged_grid_blocks(
            q, np.asarray(cs + [0] * pad) + q, qmax=_pow2(max(qs)),
            group=cfg.num_heads // cfg.num_kv_heads, page_tokens=PAGE,
            max_pages=eng.max_pages, page_bytes=page_bytes)
        every, live = every + a, live + v
    assert delta["attn.blocks.all"] == every
    assert delta["attn.blocks.live"] == live
    assert 0 < live < every


def test_fused_step_ops_carry_the_named_scopes(lm):
    eng = _engine(lm)
    bb, qb = 2, 4
    cache = {"block_table": jnp.zeros((bb, eng.max_pages), jnp.int32)}
    for p, v in zip(eng.desc.paged_planes, eng.tiered.pool_views()):
        cache["pool_" + p.name] = v
    z = jnp.zeros(bb, jnp.int32)
    text = eng._step_paged_ragged.lower(
        eng.params, cache, jnp.zeros((bb, qb), jnp.int32), z, z
    ).compile().as_text()
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/attn/", "/attn/kv_write/", "/mlp/", "/head/"):
        assert any(scope in op for op in ops), scope
    assert any("/attn/kv_write/scatter" in op for op in ops)
    assert any("/attn/jit(paged_attention_ragged)" in op for op in ops)
