"""Attention: GQA/MQA/MHA + DeepSeek MLA, with a chunked online-softmax core.

The chunked core (``chunked_attention``) is the memory-efficient XLA path used
for training/prefill (it is also the oracle for the flash_attention Pallas
kernel). Decode paths operate on KV caches; MLA decode uses the weight-absorbed
latent form (cache stores only the 512-d latent + 64-d rope key).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, dense_init, init_rmsnorm, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Core: grouped chunked online-softmax attention
# ---------------------------------------------------------------------------
def chunked_attention(q, k, v, *, scale: float, q_positions, kv_positions,
                      causal: bool, kv_valid=None, chunk_size: int = 512,
                      unroll: bool = False):
    """Online-softmax attention, scanning over KV chunks.

    q: (B, S, K, G, D) grouped queries (H = K*G)
    k, v: (B, T, K, D)
    q_positions: (B, S) int32; kv_positions: (T,) or (B, T) int32
    kv_valid: optional (B, T) bool — False entries are masked out
    Returns (B, S, K, G, D) in q.dtype.
    """
    B, S, K, G, D = q.shape
    T = k.shape[1]
    Dv = v.shape[-1]
    if T % chunk_size != 0:
        pad = chunk_size - T % chunk_size
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_positions.ndim == 1:
            kv_positions = jnp.pad(kv_positions, (0, pad))
        else:
            kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)))
        valid = jnp.ones((B, T), bool) if kv_valid is None else kv_valid
        kv_valid = jnp.pad(valid, ((0, 0), (0, pad)))
        T = T + pad
    ncnk = T // chunk_size

    if kv_positions.ndim == 1:
        kv_positions = jnp.broadcast_to(kv_positions[None, :], (B, T))
    if kv_valid is None:
        kv_valid = jnp.ones((B, T), bool)

    qf = q.astype(jnp.float32)
    kc = k.reshape(B, ncnk, chunk_size, K, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, ncnk, chunk_size, K, Dv).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(B, ncnk, chunk_size).transpose(1, 0, 2)
    mc = kv_valid.reshape(B, ncnk, chunk_size).transpose(1, 0, 2)

    m0 = jnp.full((B, K, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, S), jnp.float32)
    a0 = jnp.zeros((B, S, K, G, Dv), jnp.float32)

    def body_fixed(carry, xs):
        m, l, acc = carry
        k_j, v_j, pos_j, ok_j = xs
        s = jnp.einsum("bskgd,bckd->bkgsc", qf, k_j.astype(jnp.float32)) * scale
        allow = ok_j[:, None, :]                                   # (B, 1, C)
        if causal:
            allow = allow & (pos_j[:, None, :] <= q_positions[:, :, None])
        else:
            allow = jnp.broadcast_to(allow, (B, S, chunk_size))
        s = jnp.where(allow[:, None, None, :, :], s, NEG_INF)      # (B,K,G,S,C)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bkgsc,bckd->bskgd", p, v_j.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + upd
        return (m_new, l_new, acc_new), ()

    (m, l, acc), _ = jax.lax.scan(body_fixed, (m0, l0, a0),
                                  (kc, vc, pc, mc), unroll=unroll)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype)


def chunked_attention_tri(q, k, v, *, scale: float, chunk_size: int = 512,
                          unroll: bool = False):
    """Causal self-attention computing ONLY the lower-triangular chunk pairs.

    §Perf hillclimb (EXPERIMENTS.md): the plain chunked scan visits every
    (q-chunk, kv-chunk) pair and masks the upper triangle — ~2× wasted
    attention FLOPs at long sequence. Here the scan runs over the
    n(n+1)/2 live pairs (statically enumerated; chunks fetched with
    dynamic_index), so compiled FLOPs match the causal lower triangle.

    Requires S == T and S % chunk_size == 0 (self-attention, aligned) —
    callers fall back to ``chunked_attention`` otherwise.
    """
    B, S, K, G, D = q.shape
    C = chunk_size
    n = S // C
    qf = q.astype(jnp.float32).reshape(B, n, C, K, G, D)
    kc = k.reshape(B, n, C, K, D)
    vc = v.reshape(B, n, C, K, D)

    pairs = np.array([(i, j) for i in range(n) for j in range(i + 1)],
                     dtype=np.int32)                       # (P, 2)
    m0 = jnp.full((B, n, K, G, C), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, n, K, G, C), jnp.float32)
    a0 = jnp.zeros((B, n, C, K, G, D), jnp.float32)

    pos_in_chunk = jnp.arange(C, dtype=jnp.int32)

    def body(carry, pair):
        m, l, acc = carry
        i, j = pair[0], pair[1]
        q_i = jax.lax.dynamic_index_in_dim(qf, i, 1, keepdims=False)
        k_j = jax.lax.dynamic_index_in_dim(kc, j, 1, keepdims=False)
        v_j = jax.lax.dynamic_index_in_dim(vc, j, 1, keepdims=False)
        s = jnp.einsum("bskgd,bckd->bkgsc", q_i,
                       k_j.astype(jnp.float32)) * scale
        diag = i == j
        q_pos = i * C + pos_in_chunk
        k_pos = j * C + pos_in_chunk
        allow = jnp.where(diag, k_pos[None, :] <= q_pos[:, None], True)
        s = jnp.where(allow[None, None, None], s, NEG_INF)
        m_i = jax.lax.dynamic_index_in_dim(m, i, 1, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, i, 1, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc, i, 1, keepdims=False)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bkgsc,bckd->bskgd", p, v_j.astype(jnp.float32))
        a_new = a_i * corr.transpose(0, 3, 1, 2)[..., None] + upd
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 1)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, i, 1)
        return (m, l, acc), ()

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.asarray(pairs),
                                  unroll=unroll)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 1, 4, 2, 3)[..., None]
    return out.reshape(B, S, K, G, D).astype(q.dtype)


def full_attention(q, k, v, *, scale, q_positions, kv_positions, causal,
                   kv_valid=None):
    """Single-einsum reference attention (small shapes / decode)."""
    B, S, K, G, D = q.shape
    T = k.shape[1]
    s = jnp.einsum("bskgd,btkd->bkgst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if kv_positions.ndim == 1:
        kv_positions = jnp.broadcast_to(kv_positions[None, :], (B, T))
    allow = jnp.ones((B, S, T), bool)
    if causal:
        allow = kv_positions[:, None, :] <= q_positions[:, :, None]
    if kv_valid is not None:
        allow = allow & kv_valid[:, None, :]
    s = jnp.where(allow[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------
def init_attn(key, cfg, dtype, d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, cfg.num_heads * cfg.head_dim, dtype),
        "wk": dense_init(kk, d, cfg.num_kv_heads * cfg.head_dim, dtype),
        "wv": dense_init(kv, d, cfg.num_kv_heads * cfg.head_dim, dtype),
        "wo": dense_init(ko, cfg.num_heads * cfg.head_dim, d, dtype),
    }


def _project_qkv(params, cfg, x, positions, rope: bool):
    B, S, _ = x.shape
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    G = H // K
    q = (x @ params["wq"]).reshape(B, S, H, D)
    k = (x @ params["wk"]).reshape(B, S, K, D)
    v = (x @ params["wv"]).reshape(B, S, K, D)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = q.reshape(B, S, K, G, D)
    return q, k, v


def attn_train(params, cfg, x, positions, *, causal=True, chunk_size=512,
               unroll=False, triangular=True):
    """Self-attention over a full sequence (training / prefill compute).

    ``triangular`` routes aligned causal runs through the
    lower-triangle-only scan (half the attention FLOPs at long S).
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions, rope=True)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if S <= chunk_size:
        out = full_attention(q, k, v, scale=scale, q_positions=positions,
                             kv_positions=positions, causal=causal)
    elif causal and triangular and S % chunk_size == 0:
        out = chunked_attention_tri(q, k, v, scale=scale,
                                    chunk_size=chunk_size, unroll=unroll)
    else:
        out = chunked_attention(q, k, v, scale=scale, q_positions=positions,
                                kv_positions=positions, causal=causal,
                                chunk_size=chunk_size, unroll=unroll)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"], (k, v)


def attn_cross(params, cfg, x, enc_k, enc_v, enc_valid=None, chunk_size=512,
               unroll=False):
    """Cross-attention: queries from decoder x, keys/values precomputed."""
    B, S, _ = x.shape
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    G = H // K
    q = (x @ params["wq"]).reshape(B, S, K, G, D)
    T = enc_k.shape[1]
    pos_q = jnp.zeros((B, S), jnp.int32)
    pos_kv = jnp.zeros((T,), jnp.int32)
    fn = full_attention if max(S, T) <= chunk_size else chunked_attention
    kwargs = ({} if fn is full_attention
              else {"chunk_size": chunk_size, "unroll": unroll})
    out = fn(q, enc_k, enc_v, scale=1.0 / math.sqrt(D), q_positions=pos_q,
             kv_positions=pos_kv, causal=False, kv_valid=enc_valid, **kwargs)
    return out.reshape(B, S, H * D) @ params["wo"]


# ---------------------------------------------------------------------------
# int8 KV cache (§Perf hillclimb C): per-(token, head) symmetric scales.
# Decode is KV-read-bound; int8 halves the HBM traffic of the dominant term.
# ---------------------------------------------------------------------------
def quantize_kv(kv):
    """kv: (..., K, D) → (int8 kv, scales (..., K))."""
    scale = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(kv.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def attn_decode_q8(params, cfg, x, ck, cv, ck_s, cv_s, positions):
    """attn_decode over an int8 cache: dequant-on-read, quant-on-write."""
    B = x.shape[0]
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    pos2 = positions[:, None]
    q, k_new, v_new = _project_qkv(params, cfg, x, pos2, rope=True)
    b_idx = jnp.arange(B)
    kq, ks = quantize_kv(k_new[:, 0])
    vq, vs = quantize_kv(v_new[:, 0])
    ck = ck.at[b_idx, positions].set(kq)
    cv = cv.at[b_idx, positions].set(vq)
    ck_s = ck_s.at[b_idx, positions].set(ks)
    cv_s = cv_s.at[b_idx, positions].set(vs)
    k = dequantize_kv(ck, ck_s, x.dtype)
    v = dequantize_kv(cv, cv_s, x.dtype)
    T = k.shape[1]
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    valid = kv_pos[None, :] <= positions[:, None]
    out = full_attention(q, k, v, scale=1.0 / math.sqrt(D),
                         q_positions=pos2, kv_positions=kv_pos, causal=False,
                         kv_valid=valid)
    out = out.reshape(B, 1, H * D) @ params["wo"]
    return out, ck, cv, ck_s, cv_s


def attn_decode(params, cfg, x, cache_k, cache_v, positions):
    """Single-step decode. cache_k/v: (B, T, K, D) updated at ``positions``.

    positions: (B,) int32 — write index per sequence (also the query position).
    Returns (out, new_cache_k, new_cache_v).
    """
    B, S, _ = x.shape
    assert S == 1
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    G = H // K
    pos2 = positions[:, None]                                      # (B, 1)
    q, k, v = _project_qkv(params, cfg, x, pos2, rope=True)
    b_idx = jnp.arange(B)
    cache_k = cache_k.at[b_idx, positions].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[b_idx, positions].set(v[:, 0].astype(cache_v.dtype))
    T = cache_k.shape[1]
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    valid = kv_pos[None, :] <= positions[:, None]
    out = full_attention(q, cache_k, cache_v, scale=1.0 / math.sqrt(D),
                         q_positions=pos2, kv_positions=kv_pos, causal=False,
                         kv_valid=valid)
    out = out.reshape(B, 1, H * D) @ params["wo"]
    return out, cache_k, cache_v


def attn_decode_ragged(params, cfg, x, cache_k, cache_v, ctx_lens, q_lens):
    """Ragged multi-token decode over the dense cache (the fused mixed
    -batch tick's mirrored twin). x: (B, Qmax, d); row ``b`` appends
    ``q_lens[b]`` new tokens at positions ``ctx_lens[b] + i`` and each
    attends causally to everything at or before it. Padding slots
    (``i >= q_lens[b]``) write nothing (scatter-dropped) and their outputs
    are garbage the caller must ignore. With ``q_len == 1`` everywhere this
    is ``attn_decode`` exactly (same masks, same einsums).

    Returns (out, new_cache_k, new_cache_v).
    """
    B, Qm, _ = x.shape
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    positions = ctx_lens[:, None] + jnp.arange(Qm, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(params, cfg, x, positions, rope=True)
    T = cache_k.shape[1]
    valid = jnp.arange(Qm)[None, :] < q_lens[:, None]
    # padding slots scatter out of bounds and are dropped
    write_pos = jnp.where(valid, positions, T)
    b_idx = jnp.arange(B)[:, None]
    cache_k = cache_k.at[b_idx, write_pos].set(
        k.astype(cache_k.dtype), mode="drop")
    cache_v = cache_v.at[b_idx, write_pos].set(
        v.astype(cache_v.dtype), mode="drop")
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    out = full_attention(q, cache_k, cache_v, scale=1.0 / math.sqrt(D),
                         q_positions=positions, kv_positions=kv_pos,
                         causal=True)
    out = out.reshape(B, Qm, H * D) @ params["wo"]
    return out, cache_k, cache_v


def attn_decode_paged(params, cfg, x, pool_k, pool_v, block_table,
                      positions):
    """Single-step decode directly over a paged KV pool (mirror-free path).

    pool_k/pool_v: (P, T, K, D) — one layer's slice of the device-resident
    pool; block_table: (B, MP) int32 logical→physical mapping; positions:
    (B,) int32 write/query index. The new token's K/V is scattered into its
    page slot (each sequence owns its pages exclusively, so the (phys, slot)
    targets never collide across the batch) and attention runs through the
    ``paged_attention`` kernel over the pool — no dense per-sequence cache
    row exists anywhere.

    Returns (out, new_pool_k, new_pool_v).
    """
    from repro.kernels.paged_attention import paged_attention

    B, S, _ = x.shape
    assert S == 1
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    pos2 = positions[:, None]                                      # (B, 1)
    q, k, v = _project_qkv(params, cfg, x, pos2, rope=True)
    T = pool_k.shape[1]
    b_idx = jnp.arange(B)
    phys = block_table[b_idx, positions // T]                      # (B,)
    slot = positions % T
    pool_k = pool_k.at[phys, slot].set(k[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[phys, slot].set(v[:, 0].astype(pool_v.dtype))
    out = paged_attention(q.reshape(B, H, D), pool_k, pool_v, block_table,
                          positions + 1, scale=1.0 / math.sqrt(D))
    out = out.reshape(B, 1, H * D) @ params["wo"]
    return out, pool_k, pool_v


def attn_step_paged_ragged(params, cfg, x, pool_k, pool_v, block_table,
                           ctx_lens, q_lens):
    """Ragged multi-token step over one layer's slice of the paged KV pool
    — the fused mixed-batch tick's attention: decode rows (``q_len == 1``)
    and prefill-chunk rows (``q_len ≤ chunk``) share one launch.

    x: (B, Qmax, d_model); ctx_lens: (B,) tokens already in the pool (the
    chunk's start position); q_lens: (B,) valid new tokens per row. Each
    row's new K/V is scattered into its page slots on device (padding
    slots, including whole ``q_len == 0`` bucket-ladder rows, target an
    out-of-range page and are dropped — they can never touch another
    sequence's pages) and attention runs the ``paged_attention_ragged``
    kernel with intra-chunk causal masking against the pool.

    Returns (out, new_pool_k, new_pool_v).
    """
    from repro.kernels.paged_attention import paged_attention_ragged

    B, Qm, _ = x.shape
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    positions = ctx_lens[:, None] + jnp.arange(Qm, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(params, cfg, x, positions, rope=True)
    P, T = pool_k.shape[0], pool_k.shape[1]
    valid = jnp.arange(Qm)[None, :] < q_lens[:, None]
    logical = jnp.clip(positions // T, 0, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(block_table, logical, axis=1)       # (B, Qm)
    phys = jnp.where(valid, phys, P)               # out of range → dropped
    slot = positions % T
    with jax.named_scope("kv_write"):
        pool_k = pool_k.at[phys, slot].set(k.astype(pool_k.dtype),
                                           mode="drop")
        pool_v = pool_v.at[phys, slot].set(v.astype(pool_v.dtype),
                                           mode="drop")
    out = paged_attention_ragged(
        q.reshape(B, Qm, H, D), pool_k, pool_v, block_table,
        ctx_lens + q_lens, q_lens, scale=1.0 / math.sqrt(D))
    out = out.reshape(B, Qm, H * D) @ params["wo"]
    return out, pool_k, pool_v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------
def init_mla(key, cfg, dtype):
    m = cfg.mla
    keys = jax.random.split(key, 8)
    H = cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {}
    if m.q_lora_rank:
        p["w_dq"] = dense_init(keys[0], cfg.d_model, m.q_lora_rank, dtype)
        p["q_norm"] = init_rmsnorm(m.q_lora_rank, dtype)
        p["w_uq"] = dense_init(keys[1], m.q_lora_rank, H * qk_head, dtype)
    else:
        p["w_q"] = dense_init(keys[1], cfg.d_model, H * qk_head, dtype)
    p["w_dkv"] = dense_init(keys[2], cfg.d_model, m.kv_lora_rank, dtype)
    p["kv_norm"] = init_rmsnorm(m.kv_lora_rank, dtype)
    p["w_kr"] = dense_init(keys[3], cfg.d_model, m.qk_rope_head_dim, dtype)
    p["w_uk"] = dense_init(keys[4], m.kv_lora_rank,
                           H * m.qk_nope_head_dim, dtype).reshape(
        m.kv_lora_rank, H, m.qk_nope_head_dim)
    p["w_uv"] = dense_init(keys[5], m.kv_lora_rank,
                           H * m.v_head_dim, dtype).reshape(
        m.kv_lora_rank, H, m.v_head_dim)
    p["wo"] = dense_init(keys[6], H * m.v_head_dim, cfg.d_model, dtype)
    return p


def _mla_queries(params, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        cq = rmsnorm(params["q_norm"], x @ params["w_dq"], cfg.norm_eps)
        q = (cq @ params["w_uq"]).reshape(B, S, H, qk_head)
    else:
        q = (x @ params["w_q"]).reshape(B, S, H, qk_head)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(params, cfg, x, positions):
    m = cfg.mla
    c_kv = rmsnorm(params["kv_norm"], x @ params["w_dkv"], cfg.norm_eps)
    k_rope = (x @ params["w_kr"])[:, :, None, :]                   # (B,S,1,dr)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_train(params, cfg, x, positions, *, causal=True, chunk_size=512,
              unroll=False):
    """MLA over a full sequence. Returns (out, (c_kv, k_rope)) for caching."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_queries(params, cfg, x, positions)
    c_kv, k_rope = _mla_latent(params, cfg, x, positions)
    k_nope = jnp.einsum("btc,chd->bthd", c_kv, params["w_uk"])
    v = jnp.einsum("btc,chd->bthd", c_kv, params["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    qg = q[:, :, :, None, :]                                       # G=1
    fn = full_attention if S <= chunk_size else chunked_attention
    kwargs = ({} if fn is full_attention
              else {"chunk_size": chunk_size, "unroll": unroll})
    out = fn(qg, k, v, scale=scale, q_positions=positions,
             kv_positions=positions, causal=causal, **kwargs)
    out = out.reshape(B, S, H * m.v_head_dim)
    return out @ params["wo"], (c_kv, k_rope)


def mla_decode(params, cfg, x, cache_c, cache_kr, positions):
    """Weight-absorbed MLA decode. cache_c: (B,T,dc); cache_kr: (B,T,dr)."""
    m = cfg.mla
    B, S, _ = x.shape
    assert S == 1
    H = cfg.num_heads
    pos2 = positions[:, None]
    q_nope, q_rope = _mla_queries(params, cfg, x, pos2)
    c_new, kr_new = _mla_latent(params, cfg, x, pos2)
    b_idx = jnp.arange(B)
    cache_c = cache_c.at[b_idx, positions].set(c_new[:, 0].astype(cache_c.dtype))
    cache_kr = cache_kr.at[b_idx, positions].set(
        kr_new[:, 0].astype(cache_kr.dtype))
    # absorb W_uk into q:  (B,1,H,dn) x (dc,H,dn) -> (B,1,H,dc)
    q_c = jnp.einsum("bshd,chd->bshc", q_nope.astype(jnp.float32),
                     params["w_uk"].astype(jnp.float32))
    T = cache_c.shape[1]
    s = (jnp.einsum("bshc,btc->bhst", q_c, cache_c.astype(jnp.float32))
         + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                      cache_kr.astype(jnp.float32)))
    s = s / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    valid = kv_pos[None, :] <= positions[:, None]                  # (B, T)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bhst,btc->bshc", p, cache_c.astype(jnp.float32))
    o = jnp.einsum("bshc,chd->bshd", o_c,
                   params["w_uv"].astype(jnp.float32)).astype(x.dtype)
    out = o.reshape(B, 1, H * m.v_head_dim) @ params["wo"]
    return out, cache_c, cache_kr


def attn_decode_ragged_q8(params, cfg, x, ck, cv, ck_s, cv_s, ctx_lens,
                          q_lens):
    """``attn_decode_ragged`` over an int8 cache: the fused mixed-batch
    tick's mirrored twin for the int8 family. New tokens quantize on write
    (per (token, head), same grid as ``quantize_kv`` everywhere else),
    padding slots scatter-drop, and attention reads the dequantized cache.

    Returns (out, ck, cv, ck_s, cv_s).
    """
    B, Qm, _ = x.shape
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    positions = ctx_lens[:, None] + jnp.arange(Qm, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(params, cfg, x, positions, rope=True)
    T = ck.shape[1]
    valid = jnp.arange(Qm)[None, :] < q_lens[:, None]
    write_pos = jnp.where(valid, positions, T)
    b_idx = jnp.arange(B)[:, None]
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    ck = ck.at[b_idx, write_pos].set(kq, mode="drop")
    cv = cv.at[b_idx, write_pos].set(vq, mode="drop")
    ck_s = ck_s.at[b_idx, write_pos].set(ks, mode="drop")
    cv_s = cv_s.at[b_idx, write_pos].set(vs, mode="drop")
    kf = dequantize_kv(ck, ck_s, x.dtype)
    vf = dequantize_kv(cv, cv_s, x.dtype)
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    out = full_attention(q, kf, vf, scale=1.0 / math.sqrt(D),
                         q_positions=positions, kv_positions=kv_pos,
                         causal=True)
    out = out.reshape(B, Qm, H * D) @ params["wo"]
    return out, ck, cv, ck_s, cv_s


def attn_decode_paged_q8(params, cfg, x, pool_k, pool_v, pool_ks, pool_vs,
                         block_table, positions):
    """Single-step decode over an int8 paged pool (mirror-free): the new
    token quantizes on write into the int8 pages + scale planes, attention
    runs the dequant-in-kernel ``paged_attention_q8`` entry.

    pool_k/v: (P, T, K, D) int8; pool_ks/vs: (P, T, K) bf16.
    Returns (out, pool_k, pool_v, pool_ks, pool_vs).
    """
    from repro.kernels.paged_attention import paged_attention_q8

    B, S, _ = x.shape
    assert S == 1
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    pos2 = positions[:, None]
    q, k, v = _project_qkv(params, cfg, x, pos2, rope=True)
    T = pool_k.shape[1]
    b_idx = jnp.arange(B)
    phys = block_table[b_idx, positions // T]
    slot = positions % T
    kq, ks = quantize_kv(k[:, 0])
    vq, vs = quantize_kv(v[:, 0])
    pool_k = pool_k.at[phys, slot].set(kq)
    pool_v = pool_v.at[phys, slot].set(vq)
    pool_ks = pool_ks.at[phys, slot].set(ks)
    pool_vs = pool_vs.at[phys, slot].set(vs)
    out = paged_attention_q8(q.reshape(B, H, D), pool_k, pool_v, pool_ks,
                             pool_vs, block_table, positions + 1,
                             scale=1.0 / math.sqrt(D))
    out = out.reshape(B, 1, H * D) @ params["wo"]
    return out, pool_k, pool_v, pool_ks, pool_vs


def attn_step_paged_ragged_q8(params, cfg, x, pool_k, pool_v, pool_ks,
                              pool_vs, block_table, ctx_lens, q_lens):
    """Ragged multi-token step over one layer's slice of the int8 paged
    pool — ``attn_step_paged_ragged`` with quantize-on-write scatters into
    the int8 pages + scale planes and the ``paged_attention_ragged_q8``
    dequant-in-kernel launch.

    Returns (out, pool_k, pool_v, pool_ks, pool_vs).
    """
    from repro.kernels.paged_attention import paged_attention_ragged_q8

    B, Qm, _ = x.shape
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    positions = ctx_lens[:, None] + jnp.arange(Qm, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(params, cfg, x, positions, rope=True)
    P, T = pool_k.shape[0], pool_k.shape[1]
    valid = jnp.arange(Qm)[None, :] < q_lens[:, None]
    logical = jnp.clip(positions // T, 0, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(block_table, logical, axis=1)
    phys = jnp.where(valid, phys, P)
    slot = positions % T
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    with jax.named_scope("kv_write"):
        pool_k = pool_k.at[phys, slot].set(kq, mode="drop")
        pool_v = pool_v.at[phys, slot].set(vq, mode="drop")
        pool_ks = pool_ks.at[phys, slot].set(ks, mode="drop")
        pool_vs = pool_vs.at[phys, slot].set(vs, mode="drop")
    out = paged_attention_ragged_q8(
        q.reshape(B, Qm, H, D), pool_k, pool_v, pool_ks, pool_vs,
        block_table, ctx_lens + q_lens, q_lens, scale=1.0 / math.sqrt(D))
    out = out.reshape(B, Qm, H * D) @ params["wo"]
    return out, pool_k, pool_v, pool_ks, pool_vs


def mla_decode_ragged(params, cfg, x, cache_c, cache_kr, ctx_lens, q_lens):
    """Ragged multi-token weight-absorbed MLA decode over the dense latent
    cache — the fused tick's mirrored twin for the MLA family. Same einsum
    chain as ``mla_decode`` with a (B, Qmax) query block and intra-chunk
    causal masking; padding slots scatter-drop and their outputs are
    garbage the caller must ignore.

    Returns (out, cache_c, cache_kr).
    """
    m = cfg.mla
    B, Qm, _ = x.shape
    H = cfg.num_heads
    positions = ctx_lens[:, None] + jnp.arange(Qm, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_queries(params, cfg, x, positions)
    c_new, kr_new = _mla_latent(params, cfg, x, positions)
    T = cache_c.shape[1]
    valid = jnp.arange(Qm)[None, :] < q_lens[:, None]
    write_pos = jnp.where(valid, positions, T)
    b_idx = jnp.arange(B)[:, None]
    cache_c = cache_c.at[b_idx, write_pos].set(
        c_new.astype(cache_c.dtype), mode="drop")
    cache_kr = cache_kr.at[b_idx, write_pos].set(
        kr_new.astype(cache_kr.dtype), mode="drop")
    q_c = jnp.einsum("bshd,chd->bshc", q_nope.astype(jnp.float32),
                     params["w_uk"].astype(jnp.float32))
    s = (jnp.einsum("bshc,btc->bhst", q_c, cache_c.astype(jnp.float32))
         + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                      cache_kr.astype(jnp.float32)))
    s = s / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    allow = kv_pos[None, None, :] <= positions[:, :, None]          # (B,Qm,T)
    s = jnp.where(allow[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bhst,btc->bshc", p, cache_c.astype(jnp.float32))
    o = jnp.einsum("bshc,chd->bshd", o_c,
                   params["w_uv"].astype(jnp.float32)).astype(x.dtype)
    out = o.reshape(B, Qm, H * m.v_head_dim) @ params["wo"]
    return out, cache_c, cache_kr


def mla_decode_paged(params, cfg, x, pool_c, pool_kr, block_table,
                     positions):
    """Single-step weight-absorbed MLA decode over the paged latent pool
    (mirror-free): the new latent/rope-key scatter into their page slots
    and attention runs the ``mla_paged_attention`` entry over the latent
    plane.

    pool_c: (P, T, dc); pool_kr: (P, T, dr).
    Returns (out, pool_c, pool_kr).
    """
    from repro.kernels.paged_attention import mla_paged_attention

    m = cfg.mla
    B, S, _ = x.shape
    assert S == 1
    H = cfg.num_heads
    pos2 = positions[:, None]
    q_nope, q_rope = _mla_queries(params, cfg, x, pos2)
    c_new, kr_new = _mla_latent(params, cfg, x, pos2)
    T = pool_c.shape[1]
    b_idx = jnp.arange(B)
    phys = block_table[b_idx, positions // T]
    slot = positions % T
    pool_c = pool_c.at[phys, slot].set(c_new[:, 0].astype(pool_c.dtype))
    pool_kr = pool_kr.at[phys, slot].set(kr_new[:, 0].astype(pool_kr.dtype))
    q_c = jnp.einsum("bshd,chd->bshc", q_nope.astype(jnp.float32),
                     params["w_uk"].astype(jnp.float32))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    o_c = mla_paged_attention(q_c[:, 0], q_rope[:, 0].astype(jnp.float32),
                              pool_c, pool_kr, block_table, positions + 1,
                              scale=scale)
    o = jnp.einsum("bhc,chd->bhd", o_c.astype(jnp.float32),
                   params["w_uv"].astype(jnp.float32)).astype(x.dtype)
    out = o.reshape(B, 1, H * m.v_head_dim) @ params["wo"]
    return out, pool_c, pool_kr


def mla_step_paged_ragged(params, cfg, x, pool_c, pool_kr, block_table,
                          ctx_lens, q_lens):
    """Ragged multi-token weight-absorbed MLA step over the paged latent
    pool — the fused mixed-batch tick for the MLA family, one
    ``mla_paged_attention_ragged`` launch per layer.

    Returns (out, pool_c, pool_kr).
    """
    from repro.kernels.paged_attention import mla_paged_attention_ragged

    m = cfg.mla
    B, Qm, _ = x.shape
    H = cfg.num_heads
    positions = ctx_lens[:, None] + jnp.arange(Qm, dtype=jnp.int32)[None, :]
    q_nope, q_rope = _mla_queries(params, cfg, x, positions)
    c_new, kr_new = _mla_latent(params, cfg, x, positions)
    P, T = pool_c.shape[0], pool_c.shape[1]
    valid = jnp.arange(Qm)[None, :] < q_lens[:, None]
    logical = jnp.clip(positions // T, 0, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(block_table, logical, axis=1)
    phys = jnp.where(valid, phys, P)
    slot = positions % T
    with jax.named_scope("kv_write"):
        pool_c = pool_c.at[phys, slot].set(c_new.astype(pool_c.dtype),
                                           mode="drop")
        pool_kr = pool_kr.at[phys, slot].set(kr_new.astype(pool_kr.dtype),
                                             mode="drop")
    q_c = jnp.einsum("bshd,chd->bshc", q_nope.astype(jnp.float32),
                     params["w_uk"].astype(jnp.float32))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    o_c = mla_paged_attention_ragged(q_c, q_rope.astype(jnp.float32),
                                     pool_c, pool_kr, block_table,
                                     ctx_lens + q_lens, q_lens, scale=scale)
    o = jnp.einsum("bqhc,chd->bqhd", o_c.astype(jnp.float32),
                   params["w_uv"].astype(jnp.float32)).astype(x.dtype)
    out = o.reshape(B, Qm, H * m.v_head_dim) @ params["wo"]
    return out, pool_c, pool_kr
