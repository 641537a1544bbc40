"""Serve InternLM2-1.8B at its published widths on one TPU chip, and check it.

    python3 chip_smoke.py [--seed N]

The run goes through the entry points a user calls: ``build_model``,
``ServingEngine`` (the ``paged`` engine with its device page pool, fused
ragged ticks, the Pallas paged-attention kernels) and ``generate()``. The
weights are random, made from ``--seed``; nothing is downloaded.

The script fails, with a non-zero exit and no result line, when JAX finds no
TPU and when any check fails:

* each ragged paged-attention kernel at served widths (dense GQA in bf16,
  int8 with its scale planes, MLA at DeepSeek-V2 widths) against its jnp
  reference, within ``KERNEL_TOL``;
* the engine serves pooled and fused, the lowered fused step holds the Pallas
  kernel (``tpu_custom_call``), and no byte goes through the host mirror;
* every request gets its ``MAX_NEW`` tokens, and a second pass over the same
  requests produces the same tokens;
* for ``LOGIT_CHECKS`` requests, the served logits at every position of the
  chunk that ends the prompt against ``model.prefill`` (the dense XLA path,
  no Pallas) on the same prompt, within ``LOGIT_TOL``.

Earlier lines report compile seconds, wall seconds, tokens served, step
compiles and peak device memory. They describe this run and are not
benchmark metrics. The last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.engines import EngineSpec  # noqa: E402
from repro.kernels import (mla_paged_attention_ragged,  # noqa: E402
                           paged_attention_ragged, paged_attention_ragged_q8)
from repro.kernels.paged_attention.ref import (  # noqa: E402
    mla_paged_attention_ragged_ref, paged_attention_ragged_q8_ref,
    paged_attention_ragged_ref)
from repro.launch.compile_cache import enable_compilation_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import Request, ServeConfig, ServingEngine  # noqa: E402

ARCH = "internlm2-1.8b"
MLA_ARCH = "deepseek-v2-236b"
PAGE_TOKENS = 16
KV_HBM_BYTES = 1 << 30          # about 680 pages of 16 tokens at bf16
PREFILL_CHUNK = 256
MAX_BATCH_SEQS = 8
PROMPT_LENS = (256, 1024)       # inclusive range of seeded prompt lengths
REQUESTS = 16
MAX_NEW = 32
# Kernel vs reference, both accumulating in f32 from bf16 inputs: the
# outputs differ by the bf16 rounding of the result plus summation order.
KERNEL_TOL = 2e-2               # atol and rtol
# Served logits vs model.prefill, both bf16 through 24 layers by different
# attention code: max |diff| <= LOGIT_TOL * max |reference logit| over every
# position of the last prompt chunk. On a TPU v5e at seed 0 the served path
# differs by up to 1.6%; with the causal mask shifted by one key it differs
# by 2.7% or more, with one page per row read from another row by 19%.
LOGIT_TOL = 2e-2
LOGIT_CHECKS = 3


def device_line() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes():
    """The device's peak bytes in use so far, where the backend reports it."""
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


class CompileCounter:
    """Counts the programs JAX compiles, the persistent-cache hits among
    them, and the seconds spent, through ``jax.monitoring``."""

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

    def snapshot(self) -> tuple:
        return self.programs, self.cache_hits, self.seconds


def _ragged_batch(rng, B, Qm, MP):
    """A block table over a private pool plus ragged lengths that mix
    decode rows (q_len 1), short and full chunks, and partial pages."""
    q_lens = rng.integers(1, Qm + 1, B).astype(np.int32)
    q_lens[: B // 4] = 1
    lengths = (q_lens + rng.integers(0, PAGE_TOKENS * MP - Qm, B)).astype(
        np.int32)
    table = rng.permutation(B * MP).reshape(B, MP).astype(np.int32)
    return jnp.asarray(table), jnp.asarray(lengths), jnp.asarray(q_lens)


def _close(name: str, out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)))
    excess = float(np.max(np.abs(out - ref) - KERNEL_TOL * (1 + np.abs(ref))))
    if not np.all(np.isfinite(out)) or excess > 0:
        raise RuntimeError(f"{name} kernel differs from its reference: max "
                           f"|diff| {err} exceeds atol=rtol={KERNEL_TOL}")
    return err


def check_kernels(seed: int) -> dict:
    """Each served ragged kernel at served widths against its jnp reference
    (the reference at full f32 matmul precision). Returns the max |diff|."""
    rng = np.random.default_rng(seed)
    B, Qm, MP = 8, 16, 16
    P = B * MP
    bf = jnp.bfloat16

    def normal(*shape, dtype=bf):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    cfg = get_config(ARCH)
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    table, lengths, q_lens = _ragged_batch(rng, B, Qm, MP)
    q = normal(B, Qm, H, D)
    pk, pv = normal(P, PAGE_TOKENS, K, D), normal(P, PAGE_TOKENS, K, D)
    kq = jnp.asarray(rng.integers(-127, 128, (P, PAGE_TOKENS, K, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (P, PAGE_TOKENS, K, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.5, 1.5, (P, PAGE_TOKENS, K)) / 127, bf)
    vs = jnp.asarray(rng.uniform(0.5, 1.5, (P, PAGE_TOKENS, K)) / 127, bf)

    m = get_config(MLA_ARCH).mla
    Hm, dc, dr = get_config(MLA_ARCH).num_heads, m.kv_lora_rank, \
        m.qk_rope_head_dim
    scale = 1.0 / (m.qk_nope_head_dim + dr) ** 0.5
    qc, qr = normal(B, Qm, Hm, dc), normal(B, Qm, Hm, dr)
    pc, pkr = normal(P, PAGE_TOKENS, dc), normal(P, PAGE_TOKENS, dr)

    cases = {
        "dense": (lambda: paged_attention_ragged(q, pk, pv, table, lengths,
                                                 q_lens),
                  lambda: paged_attention_ragged_ref(q, pk, pv, table,
                                                     lengths, q_lens)),
        "int8": (lambda: paged_attention_ragged_q8(q, kq, vq, ks, vs, table,
                                                   lengths, q_lens),
                 lambda: paged_attention_ragged_q8_ref(
                     q, kq, vq, ks, vs, table, lengths, q_lens)),
        "mla": (lambda: mla_paged_attention_ragged(
                    qc, qr, pc, pkr, table, lengths, q_lens, scale=scale),
                lambda: mla_paged_attention_ragged_ref(
                    qc, qr, pc, pkr, table, lengths, q_lens, scale=scale)),
    }
    errs = {}
    for name, (kernel, reference) in cases.items():
        out = kernel()
        with jax.default_matmul_precision("highest"):
            errs[name] = _close(name, out, reference())
    return errs


def make_requests(vocab: int, n: int, max_new: int, seed: int,
                  lens: tuple) -> list:
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, vocab, int(L),
                                               dtype=np.int32),
                    max_new=max_new)
            for i, L in enumerate(rng.integers(lens[0], lens[1] + 1, n))]


def make_engine(model, params, max_len: int, kv_hbm_bytes: int,
                chunk: int) -> ServingEngine:
    return ServingEngine(model, params, ServeConfig(
        max_len=max_len, page_tokens=PAGE_TOKENS,
        engine_spec=EngineSpec(engine="paged", kv_hbm_bytes=kv_hbm_bytes),
        max_batch_seqs=MAX_BATCH_SEQS, paged_decode=True, fuse_ticks=True,
        prefill_chunk_tokens=chunk))


def capture_prompt_logits(engine: ServingEngine, prompt_lens: dict) -> dict:
    """Record, for each rid in ``prompt_lens``, the served logits at every
    position of the chunk that ends its prompt, shaped ``(chunk, V)``: the
    rows of the fused step whose chunk reaches the prompt's length. The
    engine's ``step_batch`` is wrapped; the dict fills as it serves."""
    seen: dict = {}
    step = engine.step_batch

    def recording_step(rids, caches, tok_rows, *args, **kwargs):
        out = step(rids, caches, tok_rows, *args, **kwargs)
        for i, rid in enumerate(rids):
            n = prompt_lens.get(rid)
            if n is None or rid in seen:
                continue
            if int(caches[i]["pos"][0]) + len(tok_rows[i]) == n:
                seen[rid] = np.asarray(out[0][i][0], np.float32)
        return out

    engine.step_batch = recording_step
    return seen


def serve(model, params, requests: list, max_len: int, watch: list,
          kv_hbm_bytes: int, chunk: int):
    """Serve ``requests`` through ``generate()`` on a fresh engine. Returns
    the engine, the wall seconds (the tokens are on the host, so the device
    has finished) and the served logits of the ``watch`` rids' last prompt
    chunks."""
    engine = make_engine(model, params, max_len, kv_hbm_bytes, chunk)
    if not (engine.pooled and engine.fused):
        raise RuntimeError(f"engine is not pooled and fused: pooled="
                           f"{engine.pooled} fused={engine.fused}")
    logits = capture_prompt_logits(
        engine, {r.rid: len(r.prompt) for r in requests if r.rid in watch})
    t0 = time.perf_counter()
    engine.generate(requests)
    jax.block_until_ready(engine.tiered.pool_views())
    return engine, time.perf_counter() - t0, logits


def check_served(engine: ServingEngine, requests: list) -> int:
    """Every request finished with its ``max_new`` tokens, mirror-free.
    Returns the number of tokens served."""
    short = [(r.rid, len(r.generated), r.max_new) for r in requests
             if len(r.generated) != r.max_new or not r.done]
    if short:
        raise RuntimeError(f"requests without their max_new tokens "
                           f"(rid, got, want): {short}")
    mirror = engine.stats()["mirror_d2h_bytes"]
    if mirror != 0:
        raise RuntimeError(f"pooled path moved {mirror} mirror bytes")
    return sum(len(r.generated) for r in requests)


def check_logits(model, params, requests: list, served: dict,
                 watch: list, max_len: int) -> dict:
    """The served logits of the ``watch`` rids' last prompt chunk against
    ``model.prefill`` on the same prompt, position by position. Every
    position is held to ``LOGIT_TOL`` of the reference's largest logit, so
    a wrong page or a missing causal mask inside the chunk shows even where
    the last position alone would not. Returns
    ``{rid: (max |diff|, tolerance)}``."""
    missing = [rid for rid in watch if rid not in served]
    if missing or not watch:
        raise RuntimeError(f"no served logits recorded for rids {missing} "
                           f"(watched {watch})")
    prefill = jax.jit(
        lambda p, t, n: model.prefill(p, {"tokens": t}, max_len, n)[0],
        static_argnums=2)
    V = model.cfg.vocab_size
    out = {}
    for r in requests:
        if r.rid not in watch:
            continue
        got = served[r.rid][:, :V]
        ref = np.asarray(prefill(params, jnp.asarray(r.prompt[None]),
                                 len(got)), np.float32)[0, :, :V]
        err = float(np.max(np.abs(got - ref)))
        tol = LOGIT_TOL * float(np.max(np.abs(ref)))
        if not np.all(np.isfinite(got)) or err > tol:
            raise RuntimeError(f"rid {r.rid}: served logits differ from "
                               f"model.prefill by {err} > {tol}")
        top2 = np.sort(ref, axis=-1)[:, -2:]
        decided = top2[:, 1] - top2[:, 0] > tol
        wrong = decided & (np.argmax(got, -1) != np.argmax(ref, -1))
        if np.any(wrong):
            raise RuntimeError(f"rid {r.rid}: served top-1 differs from the "
                               f"reference at chunk positions "
                               f"{np.flatnonzero(wrong).tolist()}")
        out[r.rid] = (err, tol)
    return out


def fused_step_has_kernel(model, params, engine: ServingEngine) -> bool:
    """Lower the fused step the engine jits at the largest bucket this run
    reaches and look for the Pallas kernel in the program."""
    B, Qm = MAX_BATCH_SEQS, engine.cfg.prefill_chunk_tokens
    cache = {"block_table": jnp.zeros((B, engine.max_pages), jnp.int32)}
    for p, v in zip(engine.desc.paged_planes, engine.tiered.pool_views()):
        cache["pool_" + p.name] = v
    zeros = jnp.zeros((B,), jnp.int32)
    text = jax.jit(model.step_paged_ragged).lower(
        params, cache, jnp.zeros((B, Qm), jnp.int32), zeros,
        zeros).as_text()
    return "tpu_custom_call" in text


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_line()
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke needs a TPU; JAX found {device['platform']}",
              file=sys.stderr)
        return 1
    print(f"compilation cache: {enable_compilation_cache()}", flush=True)
    compiles = CompileCounter()

    errs = check_kernels(args.seed)
    print(f"kernels vs reference, max |diff| (tol {KERNEL_TOL}): {errs}",
          flush=True)

    cfg = get_config(ARCH)
    model = build_model(cfg, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16, remat=False)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    print(f"after init: peak_bytes_in_use {peak_bytes()}", flush=True)
    max_len = PROMPT_LENS[1] + MAX_NEW
    max_len += -max_len % PAGE_TOKENS
    requests = make_requests(cfg.vocab_size, REQUESTS, MAX_NEW, args.seed,
                             PROMPT_LENS)
    watch = [r.rid for r in requests
             if len(r.prompt) > PREFILL_CHUNK][:LOGIT_CHECKS]

    n0, h0, s0 = compiles.snapshot()
    engine, wall, served_logits = serve(model, params, requests, max_len,
                                        watch, KV_HBM_BYTES, PREFILL_CHUNK)
    n1, h1, s1 = compiles.snapshot()
    tokens = check_served(engine, requests)
    stats = engine.stats()
    print(f"serve pass 1: {tokens} tokens for {len(requests)} requests in "
          f"{wall:.3f} s wall, compile {s1 - s0:.3f} s for {n1 - n0} programs "
          f"({h1 - h0} from the persistent cache), step_compiles "
          f"{stats['step_compiles']}, fused_steps {stats['fused_steps']}, "
          f"pool_pages {engine.tiered.pool_pages}, peak_bytes_in_use "
          f"{peak_bytes()}", flush=True)
    if not fused_step_has_kernel(model, params, engine):
        raise RuntimeError("the fused step lowers without tpu_custom_call: "
                           "the Pallas kernel is not in the program")
    print("fused step holds the Pallas kernel (tpu_custom_call)", flush=True)

    again = make_requests(cfg.vocab_size, REQUESTS, MAX_NEW, args.seed,
                          PROMPT_LENS)
    engine2, wall2, _ = serve(model, params, again, max_len, [],
                              KV_HBM_BYTES, PREFILL_CHUNK)
    n2, h2, s2 = compiles.snapshot()
    check_served(engine2, again)
    if [r.generated for r in again] != [r.generated for r in requests]:
        raise RuntimeError("a second pass over the same requests produced "
                           "different tokens")
    print(f"serve pass 2 (a second engine, same requests): {wall2:.3f} s "
          f"wall, {n2 - n1} programs compiled ({h2 - h1} from the "
          f"persistent cache), same tokens", flush=True)

    diffs = check_logits(model, params, requests, served_logits, watch,
                         max_len)
    print("served vs model.prefill logits, rid: (max |diff|, tol): "
          f"{diffs}", flush=True)

    print(f"peak_bytes_in_use: {peak_bytes()}; programs "
          f"compiled in all: {compiles.programs} "
          f"({compiles.cache_hits} from the persistent cache, "
          f"{compiles.seconds:.3f} s)", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
