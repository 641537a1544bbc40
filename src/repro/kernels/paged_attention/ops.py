"""jit'd public wrappers for paged decode attention.

``paged_attention`` is the single-layer form; ``paged_attention_layers`` is
the serving stack's batched multi-layer entry point (one device-resident
``(L, P, T, K, D)`` pool, one ``(B, MP)`` block table shared across layers,
ragged ``(B,)`` lengths) used by the mirror-free pooled decode path.

``paged_attention_ragged`` / ``paged_attention_layers_ragged`` extend the
same entries from one query token per row to a ragged ``(B, Qmax, H, D)``
query block with per-row ``q_lens`` — the fused mixed-batch tick: decode
rows (``q_len == 1``) and prefill-chunk rows share one kernel launch, with
causal masking *within* the chunk against the page pool.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    paged_attention_layers_pallas, paged_attention_layers_ragged_pallas,
    paged_attention_pallas, paged_attention_ragged_pallas)
from repro.kernels.paged_attention.ref import (
    paged_attention_layers_ragged_ref, paged_attention_layers_ref,
    paged_attention_ragged_ref, paged_attention_ref)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def paged_attention(q, pool_k, pool_v, block_table, lengths, *, scale=None,
                    force_pallas: bool = False):
    """Decode attention over a paged KV pool (see kernel.py)."""
    if jax.default_backend() == "tpu":
        return paged_attention_pallas(q, pool_k, pool_v, block_table, lengths,
                                      scale=scale)
    if force_pallas:
        return paged_attention_pallas(q, pool_k, pool_v, block_table, lengths,
                                      scale=scale, interpret=True)
    return paged_attention_ref(q, pool_k, pool_v, block_table, lengths,
                               scale=scale)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def paged_attention_layers(q, pool_k, pool_v, block_table, lengths, *,
                           scale=None, force_pallas: bool = False):
    """Batched multi-layer decode attention over a paged KV pool.

    q: (L, B, H, D); pool_k/v: (L, P, T, K, D); block_table: (B, MP);
    lengths: (B,). Rows with ``lengths[b] == 0`` return zeros.
    """
    if jax.default_backend() == "tpu":
        return paged_attention_layers_pallas(q, pool_k, pool_v, block_table,
                                             lengths, scale=scale)
    if force_pallas:
        return paged_attention_layers_pallas(q, pool_k, pool_v, block_table,
                                             lengths, scale=scale,
                                             interpret=True)
    return paged_attention_layers_ref(q, pool_k, pool_v, block_table,
                                      lengths, scale=scale)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def paged_attention_ragged(q, pool_k, pool_v, block_table, lengths, q_lens,
                           *, scale=None, force_pallas: bool = False):
    """Ragged-query decode attention over a paged KV pool.

    q: (B, Qmax, H, D); pool_k/v: (P, T, K, D); block_table: (B, MP);
    lengths: (B,) valid pool tokens including the chunk; q_lens: (B,) valid
    queries per row. Padding query slots and ``q_lens == 0`` rows return
    exactly zero; ``q_lens == 1`` reduces to ``paged_attention``.

    On the TPU the kernel runs one grid step per row and visits only the
    row's live (query tile × KV block) pairs: tiles of up to 128 of the
    row's ``Qmax * G`` query rows, blocks of up to 256 tokens of the row's
    pages, copied from the pool in HBM while the previous block computes
    (``kernel.py``; ``kernel.ragged_grid_blocks`` counts the pairs).
    """
    if jax.default_backend() == "tpu":
        return paged_attention_ragged_pallas(q, pool_k, pool_v, block_table,
                                             lengths, q_lens, scale=scale)
    if force_pallas:
        return paged_attention_ragged_pallas(q, pool_k, pool_v, block_table,
                                             lengths, q_lens, scale=scale,
                                             interpret=True)
    return paged_attention_ragged_ref(q, pool_k, pool_v, block_table,
                                      lengths, q_lens, scale=scale)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def paged_attention_layers_ragged(q, pool_k, pool_v, block_table, lengths,
                                  q_lens, *, scale=None,
                                  force_pallas: bool = False):
    """Batched multi-layer ragged-query attention — the fused mixed-batch
    tick's one kernel launch. q: (L, B, Qmax, H, D); pool_k/v:
    (L, P, T, K, D); block_table: (B, MP); lengths/q_lens: (B,)."""
    if jax.default_backend() == "tpu":
        return paged_attention_layers_ragged_pallas(
            q, pool_k, pool_v, block_table, lengths, q_lens, scale=scale)
    if force_pallas:
        return paged_attention_layers_ragged_pallas(
            q, pool_k, pool_v, block_table, lengths, q_lens, scale=scale,
            interpret=True)
    return paged_attention_layers_ragged_ref(q, pool_k, pool_v, block_table,
                                             lengths, q_lens, scale=scale)


# ---------------------------------------------------------------------------
# Descriptor plane variants: int8 (dequant-in-kernel, per-page scale planes)
# and MLA (attention over the latent plane). Same tpu/interpret/ref dispatch.
# ---------------------------------------------------------------------------
from repro.kernels.paged_attention.kernel import (  # noqa: E402
    mla_paged_attention_layers_ragged_pallas, mla_paged_attention_ragged_pallas,
    paged_attention_layers_ragged_q8_pallas, paged_attention_ragged_q8_pallas)
from repro.kernels.paged_attention.ref import (  # noqa: E402
    mla_paged_attention_layers_ragged_ref, mla_paged_attention_ragged_ref,
    paged_attention_layers_ragged_q8_ref, paged_attention_ragged_q8_ref)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def paged_attention_ragged_q8(q, pool_k, pool_v, pool_ks, pool_vs,
                              block_table, lengths, q_lens, *, scale=None,
                              force_pallas: bool = False):
    """Ragged-query attention over an int8 KV pool with per-(token, head)
    scale planes. q: (B, Qmax, H, D); pool_k/v: (P, T, K, D) int8;
    pool_ks/vs: (P, T, K); dequant happens in the kernel body, so pool
    pages move ~half the HBM bytes of fp16."""
    if jax.default_backend() == "tpu":
        return paged_attention_ragged_q8_pallas(
            q, pool_k, pool_v, pool_ks, pool_vs, block_table, lengths,
            q_lens, scale=scale)
    if force_pallas:
        return paged_attention_ragged_q8_pallas(
            q, pool_k, pool_v, pool_ks, pool_vs, block_table, lengths,
            q_lens, scale=scale, interpret=True)
    return paged_attention_ragged_q8_ref(q, pool_k, pool_v, pool_ks, pool_vs,
                                         block_table, lengths, q_lens,
                                         scale=scale)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def paged_attention_layers_ragged_q8(q, pool_k, pool_v, pool_ks, pool_vs,
                                     block_table, lengths, q_lens, *,
                                     scale=None, force_pallas: bool = False):
    """Multi-layer int8 ragged entry: q (L, B, Qmax, H, D); pools
    (L, P, T, K, D) int8 + (L, P, T, K) scale planes."""
    if jax.default_backend() == "tpu":
        return paged_attention_layers_ragged_q8_pallas(
            q, pool_k, pool_v, pool_ks, pool_vs, block_table, lengths,
            q_lens, scale=scale)
    if force_pallas:
        return paged_attention_layers_ragged_q8_pallas(
            q, pool_k, pool_v, pool_ks, pool_vs, block_table, lengths,
            q_lens, scale=scale, interpret=True)
    return paged_attention_layers_ragged_q8_ref(
        q, pool_k, pool_v, pool_ks, pool_vs, block_table, lengths, q_lens,
        scale=scale)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def paged_attention_q8(q, pool_k, pool_v, pool_ks, pool_vs, block_table,
                       lengths, *, scale=None, force_pallas: bool = False):
    """int8 decode entry (one query token per row): q (B, H, D). Defined as
    the ``q_len == 1`` slice of the ragged entry, so the two stay bitwise
    identical by construction."""
    B = q.shape[0]
    out = paged_attention_ragged_q8(
        q[:, None], pool_k, pool_v, pool_ks, pool_vs, block_table, lengths,
        jnp.ones((B,), jnp.int32), scale=scale, force_pallas=force_pallas)
    return out[:, 0]


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def mla_paged_attention_ragged(q_c, q_r, pool_c, pool_kr, block_table,
                               lengths, q_lens, *, scale, force_pallas=False):
    """MLA ragged entry over the latent plane. q_c: (B, Qmax, H, dc)
    weight-absorbed queries; q_r: (B, Qmax, H, dr) rope queries; pool_c:
    (P, T, dc); pool_kr: (P, T, dr). Returns the attended latent
    (B, Qmax, H, dc) — the model applies ``w_uv``/``wo`` after."""
    if jax.default_backend() == "tpu":
        return mla_paged_attention_ragged_pallas(
            q_c, q_r, pool_c, pool_kr, block_table, lengths, q_lens,
            scale=scale)
    if force_pallas:
        return mla_paged_attention_ragged_pallas(
            q_c, q_r, pool_c, pool_kr, block_table, lengths, q_lens,
            scale=scale, interpret=True)
    return mla_paged_attention_ragged_ref(q_c, q_r, pool_c, pool_kr,
                                          block_table, lengths, q_lens,
                                          scale=scale)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def mla_paged_attention_layers_ragged(q_c, q_r, pool_c, pool_kr, block_table,
                                      lengths, q_lens, *, scale,
                                      force_pallas: bool = False):
    """Multi-layer MLA ragged entry: q_c (L, B, Qmax, H, dc); q_r
    (L, B, Qmax, H, dr); pool_c (L, P, T, dc); pool_kr (L, P, T, dr)."""
    if jax.default_backend() == "tpu":
        return mla_paged_attention_layers_ragged_pallas(
            q_c, q_r, pool_c, pool_kr, block_table, lengths, q_lens,
            scale=scale)
    if force_pallas:
        return mla_paged_attention_layers_ragged_pallas(
            q_c, q_r, pool_c, pool_kr, block_table, lengths, q_lens,
            scale=scale, interpret=True)
    return mla_paged_attention_layers_ragged_ref(
        q_c, q_r, pool_c, pool_kr, block_table, lengths, q_lens, scale=scale)


@partial(jax.jit, static_argnames=("scale", "force_pallas"))
def mla_paged_attention(q_c, q_r, pool_c, pool_kr, block_table, lengths, *,
                        scale, force_pallas: bool = False):
    """MLA decode entry (one query token per row): q_c (B, H, dc); q_r
    (B, H, dr). The ``q_len == 1`` slice of the ragged entry — bitwise
    identical by construction."""
    B = q_c.shape[0]
    out = mla_paged_attention_ragged(
        q_c[:, None], q_r[:, None], pool_c, pool_kr, block_table, lengths,
        jnp.ones((B,), jnp.int32), scale=scale, force_pallas=force_pallas)
    return out[:, 0]
