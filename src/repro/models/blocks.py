"""Transformer/SSM block composition + scanned layer stacks.

Every stack is scanned over stacked (L, ...) params so the HLO contains one
``while`` body per block type (bounds compile time/memory for 40-60L full
configs; the roofline module corrects cost_analysis trip counts, DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    dense_init, init_ffn, apply_ffn, init_rmsnorm, rmsnorm)


def stack_init(init_one, key, n):
    """vmap an init over n layers → params with leading (n, ...) axis."""
    return jax.vmap(init_one)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Decoder block (dense or MoE ffn; GQA or MLA attention)
# ---------------------------------------------------------------------------
def init_decoder_block(key, cfg, dtype, *, ffn_kind: str):
    """ffn_kind: 'dense' | 'moe'."""
    k_attn, k_ffn = jax.random.split(key)
    p = {
        "ln_attn": init_rmsnorm(cfg.d_model, dtype),
        "ln_ffn": init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.mla is not None:
        p["attn"] = attn_mod.init_mla(k_attn, cfg, dtype)
    else:
        p["attn"] = attn_mod.init_attn(k_attn, cfg, dtype)
    if ffn_kind == "moe":
        p["ffn"] = moe_mod.init_moe(k_ffn, cfg, dtype)
    else:
        p["ffn"] = init_ffn(k_ffn, cfg.d_model, cfg.d_ff, cfg.ffn_activation,
                            dtype)
    return p


def apply_decoder_block(params, cfg, h, positions, *, ffn_kind: str,
                        chunk_size: int = 512, causal: bool = True,
                        ep_axes=(), unroll=False):
    """Full-sequence block. Returns (h, kv, aux_loss)."""
    rs = cfg.residual_scale
    x = rmsnorm(params["ln_attn"], h, cfg.norm_eps)
    if cfg.mla is not None:
        a, kv = attn_mod.mla_train(params["attn"], cfg, x, positions,
                                   causal=causal, chunk_size=chunk_size,
                                   unroll=unroll)
    else:
        a, kv = attn_mod.attn_train(params["attn"], cfg, x, positions,
                                    causal=causal, chunk_size=chunk_size,
                                    unroll=unroll)
    h = h + rs * a
    x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
    if ffn_kind == "moe":
        f, aux = moe_mod.apply_moe(params["ffn"], cfg, x, ep_axes=ep_axes)
    else:
        f = apply_ffn(params["ffn"], x, cfg.ffn_activation)
        aux = jnp.zeros((), jnp.float32)
    return h + rs * f, kv, aux


def decode_decoder_block(params, cfg, h, cache, positions, *, ffn_kind: str,
                         ep_axes=()):
    """Single-token block. cache: tuple of per-layer cache arrays."""
    rs = cfg.residual_scale
    x = rmsnorm(params["ln_attn"], h, cfg.norm_eps)
    if cfg.mla is not None:
        a, c0, c1 = attn_mod.mla_decode(params["attn"], cfg, x, cache[0],
                                        cache[1], positions)
    else:
        a, c0, c1 = attn_mod.attn_decode(params["attn"], cfg, x, cache[0],
                                         cache[1], positions)
    h = h + rs * a
    x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
    if ffn_kind == "moe":
        f, _ = moe_mod.apply_moe(params["ffn"], cfg, x, ep_axes=ep_axes)
    else:
        f = apply_ffn(params["ffn"], x, cfg.ffn_activation)
    return h + rs * f, (c0, c1)


def _apply_block_ffn(params, cfg, x, ffn_kind: str, ep_axes):
    if ffn_kind == "moe":
        f, _ = moe_mod.apply_moe(params["ffn"], cfg, x, ep_axes=ep_axes)
        return f
    return apply_ffn(params["ffn"], x, cfg.ffn_activation)


def decode_paged_block(params, cfg, h, planes, block_table, positions, *,
                       ffn_kind: str = "dense", ep_axes=()):
    """Single-token block over one layer's slice of the paged pool
    (mirror-free decode). ``planes`` is this layer's pool-plane tuple in
    descriptor order — ``(k, v)`` dense, ``(k, v, k_scale, v_scale)``
    int8, ``(c, kr)`` MLA — and attention dispatches on it."""
    rs = cfg.residual_scale
    x = rmsnorm(params["ln_attn"], h, cfg.norm_eps)
    if cfg.mla is not None:
        a, *planes = attn_mod.mla_decode_paged(
            params["attn"], cfg, x, planes[0], planes[1], block_table,
            positions)
    elif len(planes) == 4:
        a, *planes = attn_mod.attn_decode_paged_q8(
            params["attn"], cfg, x, planes[0], planes[1], planes[2],
            planes[3], block_table, positions)
    else:
        a, *planes = attn_mod.attn_decode_paged(
            params["attn"], cfg, x, planes[0], planes[1], block_table,
            positions)
    h = h + rs * a
    x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
    f = _apply_block_ffn(params, cfg, x, ffn_kind, ep_axes)
    return h + rs * f, tuple(planes)


def step_paged_ragged_block(params, cfg, h, planes, block_table, ctx_lens,
                            q_lens, *, ffn_kind: str = "dense", ep_axes=()):
    """Ragged multi-token block over one layer's pool-plane tuple (the
    fused mixed-batch tick). Plane dispatch as ``decode_paged_block``."""
    rs = cfg.residual_scale
    # named scopes: the layer's ops carry ``attn`` (``kv_write`` for the
    # pool scatter) or ``mlp`` in their op_name metadata, so a device trace
    # can say which part of the fused step an op belongs to
    with jax.named_scope("attn"):
        x = rmsnorm(params["ln_attn"], h, cfg.norm_eps)
        if cfg.mla is not None:
            a, *planes = attn_mod.mla_step_paged_ragged(
                params["attn"], cfg, x, planes[0], planes[1], block_table,
                ctx_lens, q_lens)
        elif len(planes) == 4:
            a, *planes = attn_mod.attn_step_paged_ragged_q8(
                params["attn"], cfg, x, planes[0], planes[1], planes[2],
                planes[3], block_table, ctx_lens, q_lens)
        else:
            a, *planes = attn_mod.attn_step_paged_ragged(
                params["attn"], cfg, x, planes[0], planes[1], block_table,
                ctx_lens, q_lens)
        h = h + rs * a
    with jax.named_scope("mlp"):
        x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
        f = _apply_block_ffn(params, cfg, x, ffn_kind, ep_axes)
        return h + rs * f, tuple(planes)


def step_ragged_block(params, cfg, h, cache, ctx_lens, q_lens, *,
                      ffn_kind: str = "dense", ep_axes=()):
    """Ragged multi-token block over the dense cache (the fused tick's
    mirrored twin). ``cache`` is this layer's plane tuple: ``(k, v)``
    dense, ``(k, v, k_scale, v_scale)`` int8, ``(c, kr)`` MLA."""
    rs = cfg.residual_scale
    x = rmsnorm(params["ln_attn"], h, cfg.norm_eps)
    if cfg.mla is not None:
        a, *cache = attn_mod.mla_decode_ragged(
            params["attn"], cfg, x, cache[0], cache[1], ctx_lens, q_lens)
    elif len(cache) == 4:
        a, *cache = attn_mod.attn_decode_ragged_q8(
            params["attn"], cfg, x, cache[0], cache[1], cache[2], cache[3],
            ctx_lens, q_lens)
    else:
        a, *cache = attn_mod.attn_decode_ragged(
            params["attn"], cfg, x, cache[0], cache[1], ctx_lens, q_lens)
    h = h + rs * a
    x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
    f = _apply_block_ffn(params, cfg, x, ffn_kind, ep_axes)
    return h + rs * f, tuple(cache)


def step_ragged_ssm_block(params, cfg, h, conv_state, ssm_state, q_lens):
    """Ragged multi-token SSM block: scan the single-step mixer over the
    Qmax query slots, masking state updates past ``q_lens`` so padding
    slots leave the state untouched. h: (B, Qmax, d). Returns
    (h, conv_steps, ssm_steps) where the ``*_steps`` carry the PER-SLOT
    states (Qmax leading axis) — the engine picks the committed slot
    (speculative rollback = picking an earlier one)."""
    B, Qm, _ = h.shape

    def body(carry, xs):
        conv, ssm = carry
        x_t, i = xs
        x = rmsnorm(params["ln"], x_t[:, None], cfg.norm_eps)
        y, (nc, ns) = ssm_mod.ssm_decode(params["mixer"], cfg, x, conv, ssm)
        live = (i < q_lens)
        nc = jnp.where(live[:, None, None], nc, conv)
        ns = jnp.where(live[:, None, None, None], ns, ssm)
        return (nc, ns), (y[:, 0], nc, ns)

    (_, _), (ys, conv_steps, ssm_steps) = jax.lax.scan(
        body, (conv_state, ssm_state),
        (h.transpose(1, 0, 2), jnp.arange(Qm, dtype=jnp.int32)))
    return h + ys.transpose(1, 0, 2), conv_steps, ssm_steps


# ---------------------------------------------------------------------------
# Encoder block (bidirectional) and enc-dec decoder block (w/ cross-attn)
# ---------------------------------------------------------------------------
def init_encoder_block(key, cfg, dtype):
    k_attn, k_ffn = jax.random.split(key)
    return {
        "ln_attn": init_rmsnorm(cfg.d_model, dtype),
        "ln_ffn": init_rmsnorm(cfg.d_model, dtype),
        "attn": attn_mod.init_attn(k_attn, cfg, dtype),
        "ffn": init_ffn(k_ffn, cfg.d_model, cfg.d_ff, cfg.ffn_activation,
                        dtype),
    }


def apply_encoder_block(params, cfg, h, positions, chunk_size=512,
                        unroll=False):
    x = rmsnorm(params["ln_attn"], h, cfg.norm_eps)
    a, _ = attn_mod.attn_train(params["attn"], cfg, x, positions,
                               causal=False, chunk_size=chunk_size,
                               unroll=unroll)
    h = h + a
    x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
    return h + apply_ffn(params["ffn"], x, cfg.ffn_activation)


def init_encdec_decoder_block(key, cfg, dtype):
    k_self, k_cross, k_ffn = jax.random.split(key, 3)
    return {
        "ln_self": init_rmsnorm(cfg.d_model, dtype),
        "ln_cross": init_rmsnorm(cfg.d_model, dtype),
        "ln_ffn": init_rmsnorm(cfg.d_model, dtype),
        "self_attn": attn_mod.init_attn(k_self, cfg, dtype),
        "cross_attn": attn_mod.init_attn(k_cross, cfg, dtype),
        "ffn": init_ffn(k_ffn, cfg.d_model, cfg.d_ff, cfg.ffn_activation,
                        dtype),
    }


def cross_kv(params, cfg, enc_out):
    """Precompute cross-attention K/V from encoder output."""
    B, T, _ = enc_out.shape
    K, D = cfg.num_kv_heads, cfg.head_dim
    k = (enc_out @ params["cross_attn"]["wk"]).reshape(B, T, K, D)
    v = (enc_out @ params["cross_attn"]["wv"]).reshape(B, T, K, D)
    return k, v


def apply_encdec_decoder_block(params, cfg, h, positions, enc_k, enc_v,
                               chunk_size=512, unroll=False):
    x = rmsnorm(params["ln_self"], h, cfg.norm_eps)
    a, kv = attn_mod.attn_train(params["self_attn"], cfg, x, positions,
                                causal=True, chunk_size=chunk_size,
                                unroll=unroll)
    h = h + a
    x = rmsnorm(params["ln_cross"], h, cfg.norm_eps)
    h = h + attn_mod.attn_cross(params["cross_attn"], cfg, x, enc_k, enc_v,
                                chunk_size=chunk_size, unroll=unroll)
    x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
    return h + apply_ffn(params["ffn"], x, cfg.ffn_activation), kv


def decode_encdec_decoder_block(params, cfg, h, cache, positions):
    ck, cv, ek, ev = cache
    x = rmsnorm(params["ln_self"], h, cfg.norm_eps)
    a, ck, cv = attn_mod.attn_decode(params["self_attn"], cfg, x, ck, cv,
                                     positions)
    h = h + a
    x = rmsnorm(params["ln_cross"], h, cfg.norm_eps)
    h = h + attn_mod.attn_cross(params["cross_attn"], cfg, x, ek, ev)
    x = rmsnorm(params["ln_ffn"], h, cfg.norm_eps)
    return h + apply_ffn(params["ffn"], x, cfg.ffn_activation), (ck, cv)


# ---------------------------------------------------------------------------
# SSM block
# ---------------------------------------------------------------------------
def init_ssm_block(key, cfg, dtype):
    return {
        "ln": init_rmsnorm(cfg.d_model, dtype),
        "mixer": ssm_mod.init_ssm(key, cfg, dtype),
    }


def apply_ssm_block(params, cfg, h, initial_state=None, unroll=False):
    x = rmsnorm(params["ln"], h, cfg.norm_eps)
    y, state = ssm_mod.apply_ssm(params["mixer"], cfg, x, initial_state,
                                 unroll=unroll)
    return h + y, state


def decode_ssm_block(params, cfg, h, conv_state, ssm_state):
    x = rmsnorm(params["ln"], h, cfg.norm_eps)
    y, (conv_state, ssm_state) = ssm_mod.ssm_decode(
        params["mixer"], cfg, x, conv_state, ssm_state)
    return h + y, conv_state, ssm_state


# ---------------------------------------------------------------------------
# Zamba2 shared block with per-invocation LoRA
# ---------------------------------------------------------------------------
def init_shared_block(key, cfg, dtype):
    """Shared attention+MLP transformer block (Zamba2)."""
    return init_decoder_block(key, cfg, dtype, ffn_kind="dense")


def init_lora(key, cfg, dtype):
    """Per-invocation LoRA on the shared block's fused qkv input projection."""
    r = cfg.hybrid.lora_rank
    qkv_out = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    k1, k2 = jax.random.split(key)
    return {
        "a": dense_init(k1, cfg.d_model, r, dtype),
        "b": jnp.zeros((r, qkv_out), dtype),
    }


def _lora_patched_attn(shared_attn, lora, cfg):
    """Return attention params with LoRA delta folded into wq/wk/wv."""
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    delta = lora["a"] @ lora["b"]                          # (d, qkv_out)
    dq, dk, dv = jnp.split(delta, [H * D, H * D + K * D], axis=-1)
    return {
        "wq": shared_attn["wq"] + dq,
        "wk": shared_attn["wk"] + dk,
        "wv": shared_attn["wv"] + dv,
        "wo": shared_attn["wo"],
    }


def apply_shared_block(shared, lora, cfg, h, positions, chunk_size=512,
                       unroll=False):
    params = dict(shared)
    params["attn"] = _lora_patched_attn(shared["attn"], lora, cfg)
    h, kv, _ = apply_decoder_block(params, cfg, h, positions,
                                   ffn_kind="dense", chunk_size=chunk_size,
                                   unroll=unroll)
    return h, kv


def decode_shared_block(shared, lora, cfg, h, cache, positions):
    params = dict(shared)
    params["attn"] = _lora_patched_attn(shared["attn"], lora, cfg)
    return decode_decoder_block(params, cfg, h, cache, positions,
                                ffn_kind="dense")
