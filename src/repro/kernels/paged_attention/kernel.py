"""Paged attention Pallas TPU kernels.

The paging design's on-device read path (DESIGN.md §2a): the KV cache lives
as fixed-size token pages in a physical pool left in HBM, and the block
table, scalar-prefetched into SMEM, drives the kernel's own page copies —
block-table indirection *inside* the kernel, the TPU analogue of NVPages'
radix-tree → page pointer walk.

One kernel body serves every dense-GQA entry: ``_pa_ragged_kernel`` over
grid ``(B,)`` (single layer) or ``(L, B)`` (multi-layer), one grid step per
row, with ``(k, v)`` pools or int8 ``(k, v)`` pools plus their scale planes.

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

**Grid and tiles.** A grid step holds one row's query block
``(K, Qmax*G, D)`` (query ``i``, group member ``g`` at row ``i*G + g``) and
walks only the row's live work, in one loop over (query tile, KV block)
pairs:

* a query tile is ``tq`` rows of the query block; tiles wholly past
  ``q_lens[b] * G`` are never visited;
* a KV block is ``ppb`` consecutive entries of the row's block table, so
  ``ppb * T`` tokens; tile ``t`` visits the blocks up to the causal horizon
  of its last live query and no further.

``block_sizes`` derives both from the launch's shapes: ``tq`` is
``Qmax*G`` up to 128 rows (the query block is padded to a whole number of
tiles), ``ppb`` the pages of 256 tokens, at most the table's width and what
keeps the double-buffered k and v blocks within 4 MiB.
``ragged_grid_blocks`` counts, with the same sizes, the pairs a launch
holds and the pairs it visits.

**KV copies.** The k and v pools stay in HBM. A block is one async copy per
live page and pool into a two-slot VMEM buffer ``(2, ppb, T, K, D)``; the
next pair's block — or, on a row's last pair, the first block of the next
grid step's row — is copied while the current one computes. Pages past a
row's length are not copied: the buffers are zeroed at the first grid
step, so such a slot holds zeros or an earlier page, finite either way,
and masked. A ``(T, K)`` page of an int8 pool's scale plane is not whole
(8, 128) tiles, which a copy out of HBM needs; so the launcher gathers each
row's scales by its table, tokens along lanes, ``(K, tokens)``, and the
pipeline brings them in with the row's query block.

**VMEM.** The KV buffers take ``2 * ppb`` pages of k and of v: 2 MiB at
InternLM2-1.8B widths (T=16, K=8, D=128, bf16: ppb=16). A query tile's
online-softmax state, per head ``m`` and ``l`` ``(tq, 1)`` and ``acc``
``(tq, D)`` in f32, takes 1.5 MiB at tq=128 and K=8 (``m`` and ``l`` pad
to 128 lanes). The pipeline double-buffers the row's query and output
blocks: 4 MiB at Qmax=256 in bf16.

**Numerics.** Q·Kᵀ takes q and the pages straight into the MXU when they
share a dtype (products of bf16 values are exact in f32, which
accumulates). int8 values are exact in q's dtype, so int8 pages meet q
there too; a token's k scale then multiplies its scores and its v scale
its probabilities before P·V. Scores, the online-softmax state, the
probabilities and P·V are f32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
Q_TILE_ROWS = 128           # query rows (queries × group) of a tile, at most
KV_BLOCK_TOKENS = 256       # tokens of a KV block, at most
KV_VMEM_BYTES = 4 << 20     # both slots of the k and v blocks, at most


def block_sizes(q_rows: int, page_tokens: int, max_pages: int,
                page_bytes: int) -> tuple:
    """``(tq, ppb)``: the query rows of a tile and the pages of a KV block,
    for rows of ``q_rows`` query rows (``Qmax * G``) over a block table
    ``max_pages`` wide, whose pages take ``page_bytes`` of k and v."""
    tq = min(q_rows, Q_TILE_ROWS)
    ppb = max(1, min(max_pages, KV_BLOCK_TOKENS // page_tokens,
                     KV_VMEM_BYTES // (2 * page_bytes)))
    return tq, ppb


def ragged_grid_blocks(q_lens, lengths, *, qmax: int, group: int,
                       page_tokens: int, max_pages: int,
                       page_bytes: int) -> tuple:
    """``(all, live)`` (query tile × KV block) pairs of one layer's launch:
    those its grid holds (every row, tile and block of the table) and those
    it visits, for rows of ``q_lens`` new queries over ``lengths`` pooled
    tokens (the new ones included), by the kernel's own ``block_sizes``."""
    q_rows = qmax * group
    tq, ppb = block_sizes(q_rows, page_tokens, max_pages, page_bytes)
    n_tiles, n_blocks = -(-q_rows // tq), -(-max_pages // ppb)
    q = np.asarray(q_lens, np.int64)[:, None]
    ln = np.asarray(lengths, np.int64)[:, None]
    t = np.arange(n_tiles)[None, :]
    last = np.minimum(q - 1, ((t + 1) * tq - 1) // group)
    blocks = np.minimum(-(-(ln - q + last + 1) // (ppb * page_tokens)),
                        n_blocks)
    visited = (t * tq < q * group) & (ln > 0)
    return (q.shape[0] * n_tiles * n_blocks,
            int(np.where(visited, blocks, 0).sum()))


def _ragged_softmax_step(s, m_ref, l_ref, acc_ref, v, v_scale=None):
    """One online-softmax update over a (QG, T) score block whose rows past
    ``q_len`` (query padding) are fully masked, into one head's state refs;
    ``v_scale`` (1, T), where given, scales each token's probability before
    P·V (int8 values). Masked probabilities are zeroed explicitly: a
    fully-masked row's running max stays NEG_INF and ``exp(s - m)`` would
    otherwise evaluate to exp(0) = 1 garbage. Written in lax primitives
    (see ``_pa_ragged_kernel``)."""
    def cols(x, shape):                     # (rows, 1) across ``shape``
        return lax.broadcast_in_dim(x, shape, (0, 1))

    m_prev = m_ref[...]
    m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(s, (1,)), (1,)))
    pr = lax.select(lax.gt(s, np.float32(NEG_INF * 0.5)),
                    lax.exp(lax.sub(s, cols(m_new, s.shape))),
                    lax.broadcast(np.float32(0), s.shape))
    corr = lax.exp(lax.sub(m_prev, m_new))
    l_ref[...] = lax.add(lax.mul(l_ref[...], corr),
                         lax.expand_dims(lax.reduce_sum(pr, (1,)), (1,)))
    if v_scale is not None:
        pr = lax.mul(pr, lax.broadcast_in_dim(v_scale, pr.shape, (0, 1)))
    acc = acc_ref[...]
    acc_ref[...] = lax.add(
        lax.mul(acc, cols(corr, acc.shape)),
        lax.dot_general(pr, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new


def _pa_ragged_kernel(table_ref, len_ref, qlen_ref, q_ref, *refs,
                      scale: float, group: int, tq: int, batch_axis: int):
    """Ragged-query body for dense and int8 pools, one grid step per row.
    ``refs`` holds the k and v pools (in HBM), for int8 pools the row's
    k and v scales ``(K, tokens)`` (VMEM blocks), the output block, the two
    two-slot KV buffers, the DMA semaphores, each head's online-softmax
    state ``m``, ``l``, ``acc`` and an SMEM pair ``(first block in flight,
    its slot)`` carried from one grid step to the next. The single-layer
    entries run it with ``batch_axis=0`` over grid (B,), the multi-layer
    ones with ``batch_axis=1`` over (L, B), the layer indexing the pools.

    The body is traced again for every program the kernel is compiled
    into, so it is written to trace cheaply: lax primitives with numpy
    scalars rather than ``jnp`` calls (each a nested jit) and Python
    scalars (each an eager device constant), and refs indexed with slices
    and traced values only."""
    K, _, D = q_ref.shape
    pools, scales = refs[:2], refs[2:len(refs) - 5 - 3 * K]
    o_ref, kbuf, vbuf, sem, *heads, state = refs[len(refs) - 5 - 3 * K:]
    m_refs, l_refs, acc_refs = heads[:K], heads[K:2 * K], heads[2 * K:]
    i32, f32 = np.int32, np.float32
    ids = [pl.program_id(i) for i in range(batch_axis + 1)]
    layer, b = (ids[0] if batch_axis else None), ids[-1]
    ppb, T = kbuf.shape[1:3]
    bk = ppb * T
    max_pages = table_ref.shape[1]
    n_blocks = -(-max_pages // ppb)
    whole_row = q_ref.shape[1] == tq        # one tile: its offset is static

    def live(row):
        return lax.bitwise_and(lax.gt(qlen_ref[row], i32(0)),
                               lax.gt(len_ref[row], i32(0)))

    def cdiv(x, d):                         # x >= 0
        return lax.div(lax.add(x, i32(d - 1)), i32(d))

    def block_copies(lyr, row, blk, slot, start):
        """Start (or wait for) the copies of block ``blk`` of ``row`` into
        ``slot``: one per live page and pool."""
        first = lax.mul(blk, i32(ppb))
        pages = lax.sub(cdiv(lax.min(len_ref[row], i32(max_pages * T)), T),
                        first)

        def copy(i, carry):
            page = table_ref[row, lax.add(first, i)]
            at = (page,) if lyr is None else (lyr, page)
            for src, buf in zip(pools, (kbuf, vbuf)):
                c = pltpu.make_async_copy(src.at[at], buf.at[slot, i],
                                          sem.at[slot])
                if start:
                    c.start()
                else:
                    c.wait()
            return carry

        lax.fori_loop(i32(0), lax.min(pages, i32(ppb)), copy, i32(0))

    @pl.when(sum(ids) == 0)
    def _first_step():
        state[0] = i32(0)
        kbuf[...] = lax.broadcast(np.zeros((), kbuf.dtype), kbuf.shape)
        vbuf[...] = lax.broadcast(np.zeros((), vbuf.dtype), vbuf.shape)

    # the next grid step's row, whose first block this row's last pair
    # prefetches when that row is live
    wrap = lax.eq(lax.add(b, i32(1)), pl.num_programs(batch_axis))
    nxt_row = lax.select(wrap, i32(0), lax.add(b, i32(1)))
    nxt_layer, has_next = None, lax.bitwise_not(wrap)
    if batch_axis:
        nxt_layer = lax.select(wrap, lax.add(layer, i32(1)), layer)
        has_next = lax.bitwise_or(has_next, lax.lt(lax.add(layer, i32(1)),
                                                   pl.num_programs(0)))
    prefetch_next = lax.bitwise_and(has_next, live(nxt_row))

    length, q_len = len_ref[b], qlen_ref[b]
    start = lax.sub(length, q_len)          # position of the row's query 0
    n_tiles = lax.select(live(b), cdiv(lax.mul(q_len, i32(group)), tq),
                         i32(0))
    o_ref[...] = lax.broadcast(np.zeros((), o_ref.dtype), o_ref.shape)
    rows_i = lax.broadcasted_iota(jnp.int32, (tq, bk), 0)
    cols_i = lax.broadcasted_iota(jnp.int32, (tq, bk), 1)

    def tile_blocks(t):
        """KV blocks tile ``t`` needs: up to its last live query's
        horizon."""
        last = lax.min(lax.sub(q_len, i32(1)),
                       lax.div(lax.sub(lax.mul(lax.add(t, i32(1)), i32(tq)),
                                       i32(1)), i32(group)))
        return lax.min(cdiv(lax.add(start, lax.add(last, i32(1))), bk),
                       i32(n_blocks))

    def pair(carry):
        t, j, slot = carry
        other = lax.sub(i32(1), slot)
        tile_done = lax.ge(lax.add(j, i32(1)), tile_blocks(t))
        t2 = lax.select(tile_done, lax.add(t, i32(1)), t)
        j2 = lax.select(tile_done, i32(0), lax.add(j, i32(1)))

        # the next pair's block, or on the row's last pair the next row's
        # first block, into the other slot
        own = lax.lt(t2, n_tiles)

        @pl.when(lax.bitwise_or(own, prefetch_next))
        def _prefetch():
            block_copies(None if layer is None else
                         lax.select(own, layer, nxt_layer),
                         lax.select(own, b, nxt_row),
                         lax.select(own, j2, i32(0)), other, True)

        @pl.when(lax.bitwise_and(lax.bitwise_not(own), prefetch_next))
        def _hand_over():
            state[0] = i32(1)
            state[1] = other

        block_copies(layer, b, j, slot, False)
        r0 = 0 if whole_row else pl.multiple_of(lax.mul(t, i32(tq)), tq)

        @pl.when(lax.eq(j, i32(0)))
        def _init():
            lowest = lax.broadcast(f32(NEG_INF), m_refs[0].shape)
            zero = lax.broadcast(f32(0), l_refs[0].shape)
            zeros = lax.broadcast(f32(0), acc_refs[0].shape)
            for m_ref, l_ref, acc_ref in zip(m_refs, l_refs, acc_refs):
                m_ref[...], l_ref[...], acc_ref[...] = lowest, zero, zeros

        # query i sits at absolute position start + i: causal within the
        # chunk against the pool; padding query slots masked out
        qi = lax.div(lax.add(rows_i, r0), i32(group))
        pos = lax.add(cols_i, lax.mul(j, i32(bk)))
        allow = lax.bitwise_and(lax.le(pos, lax.add(qi, start)),
                                lax.lt(qi, q_len))
        masked = lax.broadcast(f32(NEG_INF), (tq, bk))
        for h in range(K):
            hs = pl.ds(h, 1)
            q = lax.reshape(q_ref[hs, pl.ds(r0, tq), :], (tq, D))
            k = lax.reshape(kbuf[slot, :, :, hs, :], (bk, D))
            v = lax.convert_element_type(
                lax.reshape(vbuf[slot, :, :, hs, :], (bk, D)), jnp.float32)
            if scales:              # int8 values are exact in q's dtype
                k = lax.convert_element_type(k, q.dtype)
            elif q.dtype != k.dtype:
                q = lax.convert_element_type(q, jnp.float32)
                k = lax.convert_element_type(k, jnp.float32)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            ks, vs = [lax.convert_element_type(
                r[hs, pl.ds(lax.mul(j, i32(bk)), bk)], jnp.float32)
                for r in scales] or [None, None]                 # (1, bk)
            if ks is not None:
                s = lax.mul(s, lax.broadcast_in_dim(ks, s.shape, (0, 1)))
            s = lax.select(allow, lax.mul(s, f32(scale)), masked)
            _ragged_softmax_step(s, m_refs[h], l_refs[h], acc_refs[h], v, vs)

        @pl.when(tile_done)
        def _finish():
            for h in range(K):
                acc = acc_refs[h][...]
                out = lax.div(acc, lax.broadcast_in_dim(
                    lax.max(l_refs[h][...], f32(1e-30)), acc.shape, (0, 1)))
                o_ref[pl.ds(h, 1), pl.ds(r0, tq), :] = lax.reshape(
                    lax.convert_element_type(out, o_ref.dtype),
                    (1,) + out.shape)

        return t2, j2, other

    prefetched = lax.eq(state[0], i32(1))

    @pl.when(lax.bitwise_and(lax.gt(n_tiles, i32(0)),
                             lax.bitwise_not(prefetched)))
    def _fetch_first():
        block_copies(layer, b, i32(0), i32(0), True)

    slot0 = lax.select(prefetched, state[1], i32(0))
    state[0] = i32(0)
    lax.while_loop(lambda c: lax.lt(c[0], n_tiles), pair,
                   (i32(0), i32(0), slot0))


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
    page_bytes = sum(math.prod(p.shape[n + 1:]) * p.dtype.itemsize
                     for p in planes[:2])
    tq, ppb = block_sizes(Qm * G, T, MP, page_bytes)
    rows = -(-Qm * G // tq) * tq
    # (*lead, B, K, Qmax*G, D): one contiguous query block per row, padded
    # to a whole number of tiles
    qg = jnp.swapaxes(q.reshape(*lead, B, Qm, K, G, D), -4, -3).reshape(
        *lead, B, K, Qm * G, D)
    if rows > Qm * G:
        qg = jnp.pad(qg, [(0, 0)] * (n + 2) + [(0, rows - Qm * G), (0, 0)])
    # clamp the table so every entry is a valid physical page
    table = jnp.clip(block_table, 0, P - 1).astype(jnp.int32)
    # each row's int8 scales, gathered by the table (padded to whole
    # blocks): (*lead, B, K, tokens), tokens along lanes
    span = -(-MP // ppb) * ppb
    wide = jnp.pad(table, [(0, 0), (0, span - MP)])
    row_scales = [jnp.moveaxis(p[..., wide, :, :], -1, -3).reshape(
        *lead, B, K, span * T) for p in planes[2:]]
    grid = (lead[0], B) if n else (B,)

    def row_spec(shape):
        return pl.BlockSpec((pl.squeezed,) * (n + 1) + shape,
                            lambda *ids: ids[:n + 1] + (0,) * len(shape))

    q_spec = row_spec((K, rows, D))
    kernel = functools.partial(_pa_ragged_kernel, scale=scale, group=G,
                               tq=tq, batch_axis=n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[q_spec]
        + [pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)] * 2
        + [row_spec((K, span * T))] * len(row_scales),
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((2, ppb) + p.shape[n + 1:], p.dtype)
                        for p in planes[:2]] + [
            pltpu.SemaphoreType.DMA((2,)),
            *[pltpu.VMEM((tq, 1), jnp.float32)] * (2 * K),
            *[pltpu.VMEM((tq, D), jnp.float32)] * K,
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32), q_lens.astype(jnp.int32), qg,
      *planes[:2], *row_scales)
    out = out[..., :Qm * G, :]
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
