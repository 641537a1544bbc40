"""Paged attention Pallas TPU kernels.

The paging design's on-device read path (DESIGN.md §2a): the KV cache lives
as fixed-size token pages in a physical pool; the block table is
scalar-prefetched (SMEM) and drives the BlockSpec index maps, so each grid
step DMAs exactly one page into VMEM — block-table indirection *inside* the
kernel, the TPU analogue of NVPages' radix-tree → page pointer walk.

One kernel body serves every dense-GQA entry: ``_pa_ragged_kernel`` over
grid ``(B, MP)`` (single layer) or ``(L, B, MP)`` (multi-layer), with
``(k, v)`` pools or int8 ``(k, v)`` pools plus their scale planes.

* ``paged_attention_ragged_pallas`` / ``paged_attention_layers_ragged_pallas``
  — each row carries a block of up to ``Qmax`` new-token queries
  (``q: (B, Qmax, H, D)``) with per-row ``q_lens`` raggedness. Decode rows
  (``q_len == 1``) and prefill-chunk rows (``q_len ≤ chunk``) attend in the
  SAME launch — the fused mixed-batch tick. Query ``i`` of row ``b`` sits
  at absolute position ``lengths[b] - q_lens[b] + i`` and attends causally
  to pool positions at or before it. Slots at or past ``q_lens[b]`` produce
  exactly zero; ``q_lens[b] == 0`` rows (batch-width padding) are skipped.
* ``paged_attention_ragged_q8_pallas`` / ``..._layers_ragged_q8_pallas`` —
  the same over int8 pages, dequantized in the kernel body.
* ``paged_attention_pallas`` / ``paged_attention_layers_pallas`` — one query
  token per row: the ``q_len == 1`` slice of the ragged entries, so the two
  are bitwise identical by construction.

**Block layout.** A page block holds ALL KV heads, ``(1, T, K, D)`` over the
``(P, T, K, D)`` pool (and ``(1, T, K)`` over a ``(P, T, K)`` scale plane),
and the body loops over the K heads statically. Mosaic requires the last two
dims of a block to be multiples of (8, 128) or the full array dims; a
one-head ``(1, T, 1, D)`` block is neither, and the TPU compiler refuses it.
Queries ride as one ``(K, Qmax*G, D)`` block per row, so VMEM use grows as
``K * Qmax * G * D``: the f32 accumulator at K=8, G=2, D=128, Qmax=256 is
2 MiB.

Online-softmax state lives in VMEM scratch across the page axis. Pages past
``lengths[b]`` are skipped with ``pl.when`` (their index maps clamp to a
valid page and the body is skipped). A row with ``lengths[b] == 0`` never
runs the compute body, so its output is exactly zero — the refs mirror that
contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ragged_softmax_step(s, m_ref, l_ref, acc_ref, v, h=Ellipsis):
    """One online-softmax update over a (QG, T) score block whose rows past
    ``q_len`` (query padding) are fully masked; ``h`` selects one KV head's
    slice of the scratch. Masked probabilities are zeroed explicitly: a
    fully-masked row's running max stays NEG_INF and ``exp(s - m)`` would
    otherwise evaluate to exp(0) = 1 garbage."""
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    pr = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * corr + jnp.sum(pr, axis=1, keepdims=True)
    acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
        pr, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _pa_ragged_kernel(table_ref, len_ref, qlen_ref, q_ref, k_ref, v_ref,
                      *refs, scale: float, page_tokens: int, group: int,
                      batch_axis: int):
    """Ragged-query body for dense and int8 pools. ``refs`` is
    ``(o, m, l, acc)`` for dense pools and ``(ks, vs, o, m, l, acc)`` for
    int8 pools, whose per-(token, head) scales dequantize each page in
    fp32 — numerically the ``dequantize_kv`` grid, never materialized in
    HBM. The single-layer entries run it with ``batch_axis=0`` over grid
    (B, MP), the multi-layer ones with ``batch_axis=1`` over (L, B, MP):
    the layer axis shifts the program ids and adds a leading unit dim to
    every block."""
    if len(refs) == 6:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(batch_axis)
    p = pl.program_id(batch_axis + 1)
    last_p = pl.num_programs(batch_axis + 1) - 1
    length = len_ref[b]
    q_len = qlen_ref[b]
    lead = (0,) * (batch_axis + 1)          # the blocks' leading unit dims

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ((p * page_tokens) < length) & (q_len > 0)

    @pl.when(live)
    def _compute():
        K, QG, _ = acc_ref.shape
        kp = k_ref[lead].astype(jnp.float32)                 # (T, K, D)
        vp = v_ref[lead].astype(jnp.float32)
        if ks_ref is not None:
            ks = ks_ref[lead].astype(jnp.float32)            # (T, K)
            vs = vs_ref[lead].astype(jnp.float32)
        pos = p * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (QG, page_tokens), 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, (QG, page_tokens), 0) // group
        # query i sits at absolute position length - q_len + i: causal
        # within the chunk against the pool; padding query slots masked out
        allow = (pos <= (length - q_len + qi)) & (qi < q_len)
        for h in range(K):
            q = q_ref[lead + (h,)].astype(jnp.float32)       # (QG, D)
            k, v = kp[:, h, :], vp[:, h, :]                  # (T, D)
            if ks_ref is not None:
                k = k * ks[:, h:h + 1]
                v = v * vs[:, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(allow, s * scale, NEG_INF)
            _ragged_softmax_step(s, m_ref, l_ref, acc_ref, v, h)

    @pl.when(p == last_p)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype).reshape(o_ref.shape)


def _paged_ragged_call(q, planes, block_table, lengths, q_lens, *, scale,
                       interpret):
    """Build and run the ragged pallas_call. q: ``(*lead, B, Qmax, H, D)``
    with ``lead`` empty (single layer) or ``(L,)``; planes: the pools
    ``(*lead, P, T, K, D)`` for k and v, then for int8 pools the scale
    planes ``(*lead, P, T, K)``. Returns ``(*lead, B, Qmax, H, D)``."""
    *lead, B, Qm, H, D = q.shape
    n = len(lead)
    P, T, K = planes[0].shape[n:n + 3]
    MP = block_table.shape[1]
    G = H // K
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    # (*lead, B, K, Qmax*G, D): one contiguous query block per row
    qg = jnp.swapaxes(q.reshape(*lead, B, Qm, K, G, D), -4, -3).reshape(
        *lead, B, K, Qm * G, D)
    # clamp the table so dead pages have a valid physical index (skipped)
    table = jnp.clip(block_table, 0, P - 1).astype(jnp.int32)
    unit = (1,) * (n + 1)

    if n:
        grid = (lead[0], B, MP)

        def row_map(l, b, p, tbl, ln, ql):
            return (l, b, 0, 0, 0)

        def page_map(rank):
            return lambda l, b, p, tbl, ln, ql: (
                (l, tbl[b, p]) + (0,) * (rank - 2))
    else:
        grid = (B, MP)

        def row_map(b, p, tbl, ln, ql):
            return (b, 0, 0, 0)

        def page_map(rank):
            return lambda b, p, tbl, ln, ql: (
                (tbl[b, p],) + (0,) * (rank - 1))

    row_spec = pl.BlockSpec(unit + (K, Qm * G, D), row_map)
    page_specs = [pl.BlockSpec(unit + plane.shape[n + 1:],
                               page_map(plane.ndim)) for plane in planes]
    kernel = functools.partial(_pa_ragged_kernel, scale=scale,
                               page_tokens=T, group=G, batch_axis=n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[row_spec] + page_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((K, Qm * G, 1), jnp.float32),
            pltpu.VMEM((K, Qm * G, 1), jnp.float32),
            pltpu.VMEM((K, Qm * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32), q_lens.astype(jnp.int32), qg,
      *planes)
    return jnp.swapaxes(out.reshape(*lead, B, K, Qm, G, D), -4, -3).reshape(
        q.shape)


def paged_attention_ragged_pallas(q, pool_k, pool_v, block_table, lengths,
                                  q_lens, *, scale: float | None = None,
                                  interpret: bool = False):
    """Ragged-query entry: q (B, Qmax, H, D); pool_k/v (P, T, K, D);
    block_table (B, MP); lengths/q_lens (B,). With a leading layer axis —
    q (L, B, Qmax, H, D), pools (L, P, T, K, D) — it is the batched
    multi-layer launch, the block table and lengths shared by every layer."""
    return _paged_ragged_call(q, (pool_k, pool_v), block_table, lengths,
                              q_lens, scale=scale, interpret=interpret)


def paged_attention_ragged_q8_pallas(q, pool_k, pool_v, pool_ks, pool_vs,
                                     block_table, lengths, q_lens, *,
                                     scale: float | None = None,
                                     interpret: bool = False):
    """int8 ragged entry: q (B, Qmax, H, D); pool_k/v (P, T, K, D) int8;
    pool_ks/vs (P, T, K) scale planes; block_table (B, MP); lengths/q_lens
    (B,). A leading layer axis on q and the four planes makes it the
    multi-layer launch."""
    return _paged_ragged_call(q, (pool_k, pool_v, pool_ks, pool_vs),
                              block_table, lengths, q_lens, scale=scale,
                              interpret=interpret)


def paged_attention_pallas(q, pool_k, pool_v, block_table, lengths, *,
                           scale: float | None = None,
                           interpret: bool = False):
    """Decode entry, one query per row: q (B, H, D); pool_k/v (P, T, K, D);
    block_table (B, MP); lengths (B,). With a leading layer axis on q and
    the pools it is the multi-layer decode launch."""
    ones = jnp.ones(lengths.shape, jnp.int32)
    return paged_attention_ragged_pallas(
        jnp.expand_dims(q, -3), pool_k, pool_v, block_table, lengths, ones,
        scale=scale, interpret=interpret)[..., 0, :, :]


# The multi-layer entries are the same calls: the builders read the layer
# axis from the shapes.
paged_attention_layers_ragged_pallas = paged_attention_ragged_pallas
paged_attention_layers_ragged_q8_pallas = paged_attention_ragged_q8_pallas
paged_attention_layers_pallas = paged_attention_pallas


# ---------------------------------------------------------------------------
# MLA: attention over the latent plane (one (dc,) latent + one (dr,) rope
# key per token, shared by every head — no K axis, so its blocks span whole
# planes and need no head loop). Which entry a serving step uses comes from
# the model's CacheDescriptor (core/engines/desc.py).
# ---------------------------------------------------------------------------
def _mla_ragged_kernel(table_ref, len_ref, qlen_ref, qc_ref, qr_ref, c_ref,
                       kr_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                       page_tokens: int, heads: int, batch_axis: int):
    """MLA ragged body (weight-absorbed decode over the latent plane): one
    ``(dc,)`` latent + one ``(dr,)`` rope key per pooled token, shared by
    every query head — scores are ``q_c·cᵀ + q_r·krᵀ`` and the output is
    the probability-weighted latent (the model applies ``w_uv``/``wo``
    after). MQA-like: no K grid axis, the whole head block rides one page
    DMA of the latent."""
    b = pl.program_id(batch_axis)
    p = pl.program_id(batch_axis + 1)
    last_p = pl.num_programs(batch_axis + 1) - 1
    length = len_ref[b]
    q_len = qlen_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ((p * page_tokens) < length) & (q_len > 0)

    @pl.when(live)
    def _compute():
        dc = acc_ref.shape[-1]
        qh = acc_ref.shape[0]                                  # Qmax * H
        qc = qc_ref[...].reshape(qh, dc).astype(jnp.float32)
        qr = qr_ref[...].reshape(qh, -1).astype(jnp.float32)
        c = c_ref[...].reshape(page_tokens, dc).astype(jnp.float32)
        kr = kr_ref[...].reshape(page_tokens, -1).astype(jnp.float32)
        s = (jax.lax.dot_general(qc, c, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             ) * scale
        pos = p * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads
        allow = (pos <= (length - q_len + qi)) & (qi < q_len)
        s = jnp.where(allow, s, NEG_INF)
        _ragged_softmax_step(s, m_ref, l_ref, acc_ref, c)

    @pl.when(p == last_p)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype).reshape(o_ref.shape)


def _mla_ragged_call(q_c, q_r, pool_c, pool_kr, block_table, lengths,
                     q_lens, *, scale, interpret):
    """Build and run the MLA ragged pallas_call. q_c: ``(*lead, B, Qmax, H,
    dc)``, q_r: ``(*lead, B, Qmax, H, dr)`` with ``lead`` empty (single
    layer) or ``(L,)``; pool_c ``(*lead, P, T, dc)``, pool_kr ``(*lead, P, T,
    dr)``. Returns the attended latent ``(*lead, B, Qmax, H, dc)``."""
    *lead, B, Qm, H, dc = q_c.shape
    dr = q_r.shape[-1]
    n = len(lead)
    P, T = pool_c.shape[n:n + 2]
    MP = block_table.shape[1]
    qc = q_c.reshape(*lead, B, Qm * H, dc)
    qr = q_r.reshape(*lead, B, Qm * H, dr)
    table = jnp.clip(block_table, 0, P - 1).astype(jnp.int32)
    unit = (1,) * (n + 1)

    if n:
        grid = (lead[0], B, MP)

        def row_map(l, b, p, tbl, ln, ql):
            return (l, b, 0, 0)

        def page_map(l, b, p, tbl, ln, ql):
            return (l, tbl[b, p], 0, 0)
    else:
        grid = (B, MP)

        def row_map(b, p, tbl, ln, ql):
            return (b, 0, 0)

        def page_map(b, p, tbl, ln, ql):
            return (tbl[b, p], 0, 0)

    kernel = functools.partial(_mla_ragged_kernel, scale=scale,
                               page_tokens=T, heads=H, batch_axis=n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec(unit + (Qm * H, dc), row_map),
            pl.BlockSpec(unit + (Qm * H, dr), row_map),
            pl.BlockSpec(unit + (T, dc), page_map),
            pl.BlockSpec(unit + (T, dr), page_map),
        ],
        out_specs=pl.BlockSpec(unit + (Qm * H, dc), row_map),
        scratch_shapes=[
            pltpu.VMEM((Qm * H, 1), jnp.float32),
            pltpu.VMEM((Qm * H, 1), jnp.float32),
            pltpu.VMEM((Qm * H, dc), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qc.shape, q_c.dtype),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32), q_lens.astype(jnp.int32),
      qc, qr, pool_c, pool_kr)
    return out.reshape(q_c.shape)


def mla_paged_attention_ragged_pallas(q_c, q_r, pool_c, pool_kr, block_table,
                                      lengths, q_lens, *, scale: float,
                                      interpret: bool = False):
    """MLA ragged entry: q_c (B, Qmax, H, dc) absorbed queries; q_r
    (B, Qmax, H, dr) rope queries; pool_c (P, T, dc) latent plane; pool_kr
    (P, T, dr) rope-key plane. Returns the attended latent o_c
    (B, Qmax, H, dc). A leading layer axis on the queries and planes makes
    it the multi-layer launch."""
    return _mla_ragged_call(q_c, q_r, pool_c, pool_kr, block_table, lengths,
                            q_lens, scale=scale, interpret=interpret)


mla_paged_attention_layers_ragged_pallas = mla_paged_attention_ragged_pallas
