"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (deliverable c).

All kernels run in interpret mode on CPU (the TPU path is the same kernel
body with real BlockSpecs — see kernels/*/kernel.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:        # only the hypothesis property test skips without hypothesis —
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:          # the shape/dtype sweeps always run
    given = None

from repro.kernels import (flash_attention, log_patch, mla_paged_attention,
                           mla_paged_attention_layers_ragged,
                           mla_paged_attention_ragged, paged_attention,
                           paged_attention_layers,
                           paged_attention_layers_ragged,
                           paged_attention_layers_ragged_q8,
                           paged_attention_q8, paged_attention_ragged,
                           paged_attention_ragged_q8)
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.log_patch.ref import log_patch_ref
from repro.kernels.paged_attention.kernel import (block_sizes,
                                                  ragged_grid_blocks)
from repro.kernels.paged_attention.ref import (
    mla_paged_attention_layers_ragged_ref,
    paged_attention_layers_ragged_q8_ref, paged_attention_layers_ragged_ref,
    paged_attention_layers_ref, paged_attention_ragged_ref,
    paged_attention_ref)

_RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return _RTOL[dtype]


# ---------------------------------------------------------------- flash attn
FLASH_CASES = [
    # (B, Sq, Skv, H, K, D, causal, bq, bk)
    (2, 128, 128, 8, 2, 64, True, 64, 64),
    (1, 100, 260, 4, 4, 32, True, 32, 64),       # ragged + GQA=1
    (2, 64, 192, 6, 2, 128, False, 64, 64),      # cross-attn shape
    (1, 256, 256, 4, 1, 128, True, 128, 128),    # MQA
    (1, 37, 129, 2, 2, 256, True, 16, 32),       # gemma head_dim, unaligned
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    B, Sq, Skv, H, K, D, causal, bq, bk = case
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Skv, K, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Skv, K, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, force_pallas=True,
                          block_q=bq, block_k=bk)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=5 * _tol(dtype), rtol=_tol(dtype))


# ---------------------------------------------------------------- paged attn
PAGED_CASES = [
    # (B, H, K, D, page_tokens, pool_pages, max_pages)
    (3, 8, 4, 64, 16, 24, 6),
    (1, 4, 4, 128, 8, 8, 4),       # MHA-per-kv
    (2, 16, 2, 64, 32, 10, 4),     # large GQA group
    (4, 8, 8, 256, 16, 40, 8),     # gemma-like head_dim
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_oracle(case, dtype):
    B, H, K, D, T, P, MP = case
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    pk = jnp.asarray(rng.standard_normal((P, T, K, D)), dtype)
    pv = jnp.asarray(rng.standard_normal((P, T, K, D)), dtype)
    tbl = jnp.asarray(
        rng.permutation(P)[:B * MP].reshape(B, MP)
        if P >= B * MP else rng.integers(0, P, (B, MP)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, T * MP, B), jnp.int32)
    out = paged_attention(q, pk, pv, tbl, lens, force_pallas=True)
    ref = paged_attention_ref(q, pk, pv, tbl, lens)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=5 * _tol(dtype), rtol=2 * _tol(dtype))


# ------------------------------------------- multi-layer batched entry
LAYERS_CASES = [
    # (L, B, H, K, D, page_tokens, pool_pages, max_pages)
    (2, 3, 8, 4, 64, 16, 24, 6),
    (4, 1, 4, 4, 128, 8, 8, 4),       # single sequence
    (3, 2, 16, 2, 64, 32, 10, 4),     # large GQA group
    (1, 4, 8, 8, 256, 16, 40, 2),     # L=1 degenerate, short tables
]


@pytest.mark.parametrize("case", LAYERS_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_layers_matches_oracle(case, dtype):
    L, B, H, K, D, T, P, MP = case
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((L, B, H, D)), dtype)
    pk = jnp.asarray(rng.standard_normal((L, P, T, K, D)), dtype)
    pv = jnp.asarray(rng.standard_normal((L, P, T, K, D)), dtype)
    tbl = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, T * MP, B), jnp.int32)
    out = paged_attention_layers(q, pk, pv, tbl, lens, force_pallas=True)
    ref = paged_attention_layers_ref(q, pk, pv, tbl, lens)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=5 * _tol(dtype), rtol=2 * _tol(dtype))


@pytest.mark.parametrize("entry", ["single", "layers"])
def test_paged_attention_contract_edges(entry):
    """The block-table contract's edge rows in one batch: an empty row
    (exactly-zero output), a single-token row, a single-page row, and a
    ragged mid-page row — Pallas and oracle must agree on all of them."""
    L, B, H, K, D, T, P, MP = 2, 4, 8, 4, 64, 8, 24, 4
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((L, B, H, D)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    tbl = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    lens = jnp.asarray([0, 1, T, T * MP - 3], jnp.int32)
    if entry == "single":
        out = paged_attention(q[0], pk[0], pv[0], tbl, lens,
                              force_pallas=True)
        ref = paged_attention_ref(q[0], pk[0], pv[0], tbl, lens)
        empty = np.asarray(out)[0]
    else:
        out = paged_attention_layers(q, pk, pv, tbl, lens,
                                     force_pallas=True)
        ref = paged_attention_layers_ref(q, pk, pv, tbl, lens)
        empty = np.asarray(out)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=2e-5)
    assert np.all(empty == 0.0), "empty rows must produce exactly zero"


# ----------------------------------------------------- ragged-query entries
RAGGED_CASES = [
    # (L, B, Qmax, H, K, D, page_tokens, pool_pages, max_pages), lengths
    # and q_lens drawn from the seed
    (2, 3, 4, 8, 4, 64, 16, 24, 6),
    (1, 1, 8, 4, 4, 128, 8, 8, 4),      # one long chunk row
    (3, 2, 2, 16, 2, 64, 32, 10, 4),    # large GQA group
    (2, 4, 1, 8, 8, 256, 16, 40, 4),    # Qmax=1 degenerate (pure decode)
]
# The kernel's tiles and blocks at work: the same tuple, then the lengths
# and q_lens. At 16-token pages a KV block is 16 pages (256 tokens), and a
# query tile is 128 rows of Qmax * G.
TILED_CASES = [
    # a decode row beside a 256-token chunk row (4 tiles, 2 blocks) in one
    # launch, and a q_len = 0 row with a nonzero length
    (2, 3, 256, 4, 2, 64, 16, 60, 20, [300, 40 + 256, 7], [1, 256, 0]),
    # lengths ending mid-page past a block, on the block boundary, one page
    # past it, and a row with fewer live pages than one block
    (2, 4, 4, 8, 4, 64, 16, 96, 24, [256 + 7, 256, 256 + 16, 40],
     [3, 4, 1, 2]),
    # Qmax * G = 144 rows: a whole tile and a part of one
    (1, 2, 72, 4, 2, 64, 16, 40, 20, [72 + 100, 30], [72, 5]),
]


def _ragged_inputs(case, dtype, seed=12):
    L, B, Qm, H, K, D, T, P, MP = case[:9]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((L, B, Qm, H, D)), dtype)
    pk = jnp.asarray(rng.standard_normal((L, P, T, K, D)), dtype)
    pv = jnp.asarray(rng.standard_normal((L, P, T, K, D)), dtype)
    tbl = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    qls = rng.integers(1, Qm + 1, B).astype(np.int32)
    lens = (rng.integers(0, T * MP - Qm, B) + qls).astype(np.int32)
    if len(case) > 9:
        lens, qls = (np.asarray(x, np.int32) for x in case[9:])
    return q, pk, pv, tbl, jnp.asarray(lens), jnp.asarray(qls)


def _assert_padding_zero(out, qls):
    """Exact zeros in every query slot at or past a row's q_len (the
    whole row where q_len is 0); ``out`` is (L, B, Qmax, H, D)."""
    for b, n in enumerate(np.asarray(qls)):
        assert np.all(out[:, b, int(n):] == 0.0), b


@pytest.mark.parametrize("case", RAGGED_CASES + TILED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_ragged_matches_oracle(case, dtype):
    q, pk, pv, tbl, lens, qls = _ragged_inputs(case, dtype)
    out = paged_attention_ragged(q[0], pk[0], pv[0], tbl, lens, qls,
                                 force_pallas=True)
    ref = paged_attention_ragged_ref(q[0], pk[0], pv[0], tbl, lens, qls)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=5 * _tol(dtype), rtol=2 * _tol(dtype))
    _assert_padding_zero(np.asarray(out)[None], qls)


@pytest.mark.parametrize("case", RAGGED_CASES + TILED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_layers_ragged_matches_oracle(case, dtype):
    q, pk, pv, tbl, lens, qls = _ragged_inputs(case, dtype)
    out = paged_attention_layers_ragged(q, pk, pv, tbl, lens, qls,
                                        force_pallas=True)
    ref = paged_attention_layers_ragged_ref(q, pk, pv, tbl, lens, qls)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=5 * _tol(dtype), rtol=2 * _tol(dtype))
    _assert_padding_zero(np.asarray(out), qls)


@pytest.mark.parametrize("q_rows,page_tokens,max_pages,page_bytes", [
    (2, 16, 160, 65536),          # InternLM2-1.8B decode program, bf16
    (512, 16, 160, 65536),        # its (8, 256) chunk program
    (512, 16, 160, 32768),        # the same over int8 pages
    (144, 8, 40, 4096),           # a tile and a part of one
    (6, 32, 4, 8192),             # a table narrower than one block
    (8, 16, 64, 1 << 20),         # pages the VMEM budget caps
])
def test_ragged_grid_blocks_is_a_brute_force_count(q_rows, page_tokens,
                                                   max_pages, page_bytes):
    """The host count of (query tile × KV block) pairs equals a brute-force
    walk over every pair of every row: a pair is visited when one of the
    tile's live query rows attends a position inside the block."""
    group = 2
    qmax = q_rows // group
    tq, ppb = block_sizes(q_rows, page_tokens, max_pages, page_bytes)
    bk, cap = ppb * page_tokens, max_pages * page_tokens
    rng = np.random.default_rng(40)
    q_lens = np.concatenate([[0, 1, qmax], rng.integers(0, qmax + 1, 9)])
    lengths = np.minimum(q_lens + rng.integers(0, cap, 12), cap)
    lengths[0] = 5                     # q_len 0 with pooled tokens
    n_tiles, n_blocks = -(-q_rows // tq), -(-max_pages // ppb)
    live = 0
    for q, ln in zip(q_lens, lengths):
        for t in range(n_tiles):
            rows = [r for r in range(t * tq, (t + 1) * tq)
                    if r < q * group]
            for j in range(n_blocks):
                live += any(j * bk <= ln - q + r // group
                            for r in rows)
    got = ragged_grid_blocks(q_lens, lengths, qmax=qmax, group=group,
                             page_tokens=page_tokens, max_pages=max_pages,
                             page_bytes=page_bytes)
    assert got == (len(q_lens) * n_tiles * n_blocks, live)
    assert 0 < live < got[0]


def test_ragged_qlen1_is_bitwise_decode_kernel():
    """The fused entries at q_len=1 must be the plain decode entries BIT
    FOR BIT — the contract that lets the batched decode launch route
    through the ragged step without a numerics audit."""
    L, B, H, K, D, T, P, MP = 2, 4, 8, 4, 64, 8, 24, 4
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((L, B, 1, H, D)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    tbl = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    lens = jnp.asarray([1, 7, T, T * MP - 2], jnp.int32)
    qls = jnp.ones(B, jnp.int32)
    r1 = paged_attention_ragged(q[0], pk[0], pv[0], tbl, lens, qls,
                                force_pallas=True)
    d1 = paged_attention(q[0, :, 0], pk[0], pv[0], tbl, lens,
                         force_pallas=True)
    assert np.array_equal(np.asarray(r1[:, 0]), np.asarray(d1))
    rl = paged_attention_layers_ragged(q, pk, pv, tbl, lens, qls,
                                       force_pallas=True)
    dl = paged_attention_layers(q[:, :, 0], pk, pv, tbl, lens,
                                force_pallas=True)
    assert np.array_equal(np.asarray(rl[:, :, 0]), np.asarray(dl))


@pytest.mark.parametrize("entry", ["single", "layers"])
def test_ragged_contract_edges(entry):
    """Ragged contract edges in one batch: a q_len=0 padding row (exactly
    zero even with a nonzero length), a decode row, a chunk ending exactly
    on a page boundary, and a ragged mid-page chunk — plus exact zeros in
    every padding query slot."""
    L, B, Qm, H, K, D, T, P, MP = 2, 4, 4, 8, 4, 64, 8, 24, 4
    rng = np.random.default_rng(14)
    q = jnp.asarray(rng.standard_normal((L, B, Qm, H, D)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    tbl = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    lens = jnp.asarray([6, 5, 2 * T, T * MP - 3], jnp.int32)
    qls = jnp.asarray([0, 1, T // 2, 3], jnp.int32)
    if entry == "single":
        out = paged_attention_ragged(q[0], pk[0], pv[0], tbl, lens, qls,
                                     force_pallas=True)
        ref = paged_attention_ragged_ref(q[0], pk[0], pv[0], tbl, lens, qls)
        o = np.asarray(out)[None]
    else:
        out = paged_attention_layers_ragged(q, pk, pv, tbl, lens, qls,
                                            force_pallas=True)
        ref = paged_attention_layers_ragged_ref(q, pk, pv, tbl, lens, qls)
        o = np.asarray(out)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=2e-5)
    for b in range(B):
        assert np.all(o[:, b, int(qls[b]):] == 0.0), b


def test_ragged_ignores_dead_pages():
    """Poisoning pages and slots past each row's length must not change the
    ragged output — per-query masking against the pool is exact."""
    L, B, Qm, H, K, D, T, MP = 2, 2, 4, 4, 2, 64, 16, 4
    P = B * MP
    rng = np.random.default_rng(15)
    lens = [7, 39]
    qls = jnp.asarray([2, 4], jnp.int32)
    q = jnp.asarray(rng.standard_normal((L, B, Qm, H, D)), jnp.float32)
    pk = np.asarray(rng.standard_normal((L, P, T, K, D)), np.float32)
    pv = np.asarray(rng.standard_normal((L, P, T, K, D)), np.float32)
    tbl = np.arange(P, dtype=np.int32).reshape(B, MP)
    lens_arr = jnp.asarray(lens, jnp.int32)
    out1 = paged_attention_layers_ragged(q, jnp.asarray(pk), jnp.asarray(pv),
                                         jnp.asarray(tbl), lens_arr, qls,
                                         force_pallas=True)
    pk2, pv2 = pk.copy(), pv.copy()
    for b in range(B):
        for lp in range(MP):
            phys = tbl[b, lp]
            start = lp * T
            if start >= lens[b]:
                pk2[:, phys] = 1e6
                pv2[:, phys] = -1e6
            elif start + T > lens[b]:
                pk2[:, phys, lens[b] - start:] = 1e6
                pv2[:, phys, lens[b] - start:] = -1e6
    out2 = paged_attention_layers_ragged(q, jnp.asarray(pk2),
                                         jnp.asarray(pv2), jnp.asarray(tbl),
                                         lens_arr, qls, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


def test_paged_attention_layers_ignores_dead_pages():
    """Poisoning pool pages past each sequence's length must not change the
    multi-layer entry's output (per-layer masking is exact)."""
    L, B, H, K, D, T, MP = 2, 2, 4, 2, 64, 16, 4
    P = B * MP
    rng = np.random.default_rng(8)
    lens = [5, 37]
    q = jnp.asarray(rng.standard_normal((L, B, H, D)), jnp.float32)
    pk = np.asarray(rng.standard_normal((L, P, T, K, D)), np.float32)
    pv = np.asarray(rng.standard_normal((L, P, T, K, D)), np.float32)
    tbl = np.arange(P, dtype=np.int32).reshape(B, MP)
    lens_arr = jnp.asarray(lens, jnp.int32)
    out1 = paged_attention_layers(q, jnp.asarray(pk), jnp.asarray(pv),
                                  jnp.asarray(tbl), lens_arr,
                                  force_pallas=True)
    pk2, pv2 = pk.copy(), pv.copy()
    for b in range(B):
        for lp in range(MP):
            phys = tbl[b, lp]
            start = lp * T
            if start >= lens[b]:
                pk2[:, phys] = 1e6
                pv2[:, phys] = -1e6
            elif start + T > lens[b]:
                pk2[:, phys, lens[b] - start:] = 1e6
                pv2[:, phys, lens[b] - start:] = -1e6
    out2 = paged_attention_layers(q, jnp.asarray(pk2), jnp.asarray(pv2),
                                  jnp.asarray(tbl), lens_arr,
                                  force_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


def _dead_pages_body(lens):
    """Poisoning pool pages past each sequence's length must not change the
    output (the kernel's length masking / pl.when skip is exact)."""
    B, H, K, D, T, MP = 2, 4, 2, 64, 16, 4
    P = B * MP                                     # disjoint tables
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    pk = np.asarray(rng.standard_normal((P, T, K, D)), np.float32)
    pv = np.asarray(rng.standard_normal((P, T, K, D)), np.float32)
    tbl = np.arange(P, dtype=np.int32).reshape(B, MP)
    lens_arr = jnp.asarray(lens, jnp.int32)
    out1 = paged_attention(q, jnp.asarray(pk), jnp.asarray(pv),
                           jnp.asarray(tbl), lens_arr, force_pallas=True)
    pk2, pv2 = pk.copy(), pv.copy()
    for b in range(B):
        for lp in range(MP):
            phys = tbl[b, lp]
            start = lp * T
            if start >= lens[b]:                   # fully dead page
                pk2[phys] = 1e6
                pv2[phys] = -1e6
            elif start + T > lens[b]:              # partially dead slots
                pk2[phys, lens[b] - start:] = 1e6
                pv2[phys, lens[b] - start:] = -1e6
    out2 = paged_attention(q, jnp.asarray(pk2), jnp.asarray(pv2),
                           jnp.asarray(tbl), lens_arr, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


@pytest.mark.parametrize("lens", [[1, 63], [16, 16], [7, 40]])
def test_paged_attention_ignores_dead_pages_fixed(lens):
    _dead_pages_body(lens)


if given is not None:
    @given(lens=st.lists(st.integers(1, 63), min_size=2, max_size=2))
    @settings(max_examples=10)
    def test_paged_attention_ignores_dead_pages(lens):
        _dead_pages_body(lens)


def test_ragged_speculative_block_bitwise_vs_sequential_decode():
    """A speculative decode row — q_len = 1 + k query slots over KV that
    was already scattered for the whole block — must equal 1 + k
    SUCCESSIVE commit-one-more-slot launches bit for bit: the launch with
    ``lengths = base + i + 1, q_lens = i + 1`` (what a sequential tick
    sequence sees after committing ``i`` tokens) reproduces slots
    ``0..i`` of the full block exactly. The successive launches keep the
    padded query shape fixed — crossing shapes changes the score-matmul
    reduction order by a ulp, which is why the shape-crossing pin lives
    at q_len=1 (``test_ragged_qlen1_is_bitwise_decode_kernel``). Against
    the plain decode entry, slot ``i`` at position ``lengths - q_len + i``
    matches a decode of length ``base + i + 1`` to float32 tolerance."""
    L, B, H, K, D, T, P, MP = 2, 3, 8, 4, 64, 8, 24, 4
    S = 4                                       # 1 real + 3 draft slots
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.standard_normal((L, B, S, H, D)), jnp.float32)
    pk = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((L, P, T, K, D)), jnp.float32)
    tbl = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    base = np.asarray([3, T - 1, 2 * T + 5], np.int32)  # pre-block lengths
    out = paged_attention_ragged(q[0], pk[0], pv[0], tbl,
                                 jnp.asarray(base + S),
                                 jnp.full(B, S, jnp.int32),
                                 force_pallas=True)
    outl = paged_attention_layers_ragged(q, pk, pv, tbl,
                                         jnp.asarray(base + S),
                                         jnp.full(B, S, jnp.int32),
                                         force_pallas=True)
    for i in range(S):
        li = jnp.asarray(base + i + 1)
        qi = jnp.full(B, i + 1, jnp.int32)
        oi = paged_attention_ragged(q[0], pk[0], pv[0], tbl, li, qi,
                                    force_pallas=True)
        assert np.array_equal(np.asarray(out[:, :i + 1]),
                              np.asarray(oi[:, :i + 1])), i
        oli = paged_attention_layers_ragged(q, pk, pv, tbl, li, qi,
                                            force_pallas=True)
        assert np.array_equal(np.asarray(outl[:, :, :i + 1]),
                              np.asarray(oli[:, :, :i + 1])), i
        d = paged_attention(q[0, :, i], pk[0], pv[0], tbl, li,
                            force_pallas=True)
        np.testing.assert_allclose(np.asarray(out[:, i]), np.asarray(d),
                                   atol=1e-6, rtol=1e-6)
        dl = paged_attention_layers(q[:, :, i], pk, pv, tbl, li,
                                    force_pallas=True)
        np.testing.assert_allclose(np.asarray(outl[:, :, i]),
                                   np.asarray(dl), atol=1e-6, rtol=1e-6)


def test_ragged_rolled_back_draft_slots_are_invisible():
    """Rollback leaves rejected draft KV inside retained pool pages and
    stale block-table tail entries pointing at freed pages — the next
    launch must see neither. Poisoning every slot at or past the
    committed length, every fully dead page, AND repointing the stale
    table tail at a garbage page changes nothing (lengths is the only
    visibility authority, same discipline as padding scatter)."""
    L, B, Qm, H, K, D, T, MP = 2, 2, 4, 4, 2, 64, 8, 4
    P = B * MP + 1                 # disjoint tables + one garbage page
    rng = np.random.default_rng(22)
    cl = [9, 19]                   # committed lengths after rollback
    qls = jnp.asarray([1, 3], jnp.int32)      # next tick speculates again
    q = jnp.asarray(rng.standard_normal((L, B, Qm, H, D)), jnp.float32)
    pk = np.asarray(rng.standard_normal((L, P, T, K, D)), np.float32)
    pv = np.asarray(rng.standard_normal((L, P, T, K, D)), np.float32)
    tbl = np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    lens_arr = jnp.asarray(cl, jnp.int32)
    out1 = paged_attention_layers_ragged(q, jnp.asarray(pk), jnp.asarray(pv),
                                         jnp.asarray(tbl), lens_arr, qls,
                                         force_pallas=True)
    pk2, pv2 = pk.copy(), pv.copy()
    tbl2 = tbl.copy()
    pk2[:, P - 1] = 1e6            # the garbage page stale entries hit
    pv2[:, P - 1] = -1e6
    for b in range(B):
        for lp in range(MP):
            phys = tbl[b, lp]
            start = lp * T
            if start >= cl[b]:                 # page freed by the rewind
                pk2[:, phys] = 1e6
                pv2[:, phys] = -1e6
                tbl2[b, lp] = P - 1            # stale table tail entry
            elif start + T > cl[b]:            # rejected tail in a kept page
                pk2[:, phys, cl[b] - start:] = 1e6
                pv2[:, phys, cl[b] - start:] = -1e6
    out2 = paged_attention_layers_ragged(q, jnp.asarray(pk2),
                                         jnp.asarray(pv2), jnp.asarray(tbl2),
                                         lens_arr, qls, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


# ------------------------------------- descriptor-plane entries (int8 + MLA)
def _q8_inputs(seed=31):
    """Small int8 pool + bf16 per-(token, head) scale planes, ragged batch
    with a padding row (q_len = 0), a decode row, and two chunk rows."""
    L, B, Qm, H, K, D, T, MP = 2, 4, 3, 4, 2, 64, 8, 3
    P = B * MP                                       # disjoint tables
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((L, B, Qm, H, D)), jnp.float32)
    pk = jnp.asarray(rng.integers(-127, 128, (L, P, T, K, D)), jnp.int8)
    pv = jnp.asarray(rng.integers(-127, 128, (L, P, T, K, D)), jnp.int8)
    ks = jnp.asarray(rng.random((L, P, T, K)) * 0.1 + 0.01, jnp.bfloat16)
    vs = jnp.asarray(rng.random((L, P, T, K)) * 0.1 + 0.01, jnp.bfloat16)
    tbl = jnp.asarray(np.arange(P, dtype=np.int32).reshape(B, MP))
    lens = jnp.asarray([6, 5, T, T * MP - 2], jnp.int32)
    qls = jnp.asarray([0, 1, 2, 3], jnp.int32)
    return q, pk, pv, ks, vs, tbl, lens, qls


def _mla_inputs(seed=32):
    """Latent + rope-key planes (no KV-head axis), same ragged batch edges."""
    L, B, Qm, H, dc, dr, T, MP = 2, 4, 3, 4, 64, 32, 8, 3
    P = B * MP
    rng = np.random.default_rng(seed)
    q_c = jnp.asarray(rng.standard_normal((L, B, Qm, H, dc)), jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((L, B, Qm, H, dr)), jnp.float32)
    pc = jnp.asarray(rng.standard_normal((L, P, T, dc)), jnp.float32)
    pkr = jnp.asarray(rng.standard_normal((L, P, T, dr)), jnp.float32)
    tbl = jnp.asarray(np.arange(P, dtype=np.int32).reshape(B, MP))
    lens = jnp.asarray([6, 5, T, T * MP - 2], jnp.int32)
    qls = jnp.asarray([0, 1, 2, 3], jnp.int32)
    scale = float(1.0 / np.sqrt(dc + dr))
    return q_c, q_r, pc, pkr, tbl, lens, qls, scale


def _q8_tiled_inputs(case, seed=38):
    """int8 pools and scale planes at a ``TILED_CASES`` case."""
    L, B, Qm, H, K, D, T, P, MP, lens, qls = case
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((L, B, Qm, H, D)), jnp.float32)
    pk = jnp.asarray(rng.integers(-127, 128, (L, P, T, K, D)), jnp.int8)
    pv = jnp.asarray(rng.integers(-127, 128, (L, P, T, K, D)), jnp.int8)
    ks = jnp.asarray(rng.random((L, P, T, K)) * 0.1 + 0.01, jnp.bfloat16)
    vs = jnp.asarray(rng.random((L, P, T, K)) * 0.1 + 0.01, jnp.bfloat16)
    tbl = jnp.asarray(rng.integers(0, P, (B, MP)), jnp.int32)
    return (q, pk, pv, ks, vs, tbl, jnp.asarray(lens, jnp.int32),
            jnp.asarray(qls, jnp.int32))


@pytest.mark.parametrize("case", [None] + TILED_CASES)
def test_q8_ragged_matches_oracle_and_pads_zero(case):
    """int8 ragged entries vs the pure-jnp oracle, plus exact zeros in every
    padding query slot (q_len = 0 rows included): the small batch, and the
    kernel's tiles and blocks at work."""
    q, pk, pv, ks, vs, tbl, lens, qls = (
        _q8_inputs() if case is None else _q8_tiled_inputs(case))
    out = paged_attention_layers_ragged_q8(q, pk, pv, ks, vs, tbl, lens,
                                           qls, force_pallas=True)
    ref = paged_attention_layers_ragged_q8_ref(q, pk, pv, ks, vs, tbl,
                                               lens, qls)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=4e-5)
    o1 = paged_attention_ragged_q8(q[0], pk[0], pv[0], ks[0], vs[0], tbl,
                                   lens, qls, force_pallas=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(out)[0],
                               atol=1e-4, rtol=4e-5)
    o = np.asarray(out)
    for b in range(o.shape[1]):
        assert np.all(o[:, b, int(qls[b]):] == 0.0), b


def test_q8_dequant_parity_vs_fp32_oracle():
    """Kernel-body dequant (int8 × bf16 scale → fp32) must agree with the
    dense fp32 oracle run over a MANUALLY dequantized pool — the pin that
    the half-bytes pool read does not change the numerics contract."""
    q, pk, pv, ks, vs, tbl, lens, qls = _q8_inputs(seed=33)
    deq_k = pk.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
    deq_v = pv.astype(jnp.float32) * vs.astype(jnp.float32)[..., None]
    out = paged_attention_layers_ragged_q8(q, pk, pv, ks, vs, tbl, lens,
                                           qls, force_pallas=True)
    ref = paged_attention_layers_ragged_ref(q, deq_k, deq_v, tbl, lens, qls)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=4e-5)


def test_q8_qlen1_is_bitwise_decode_entry():
    """q_len = 1 through the int8 ragged entries IS the int8 decode entry
    bit for bit, and the multi-layer launch is bitwise the stacked
    single-layer launches — no numerics audit needed to route batched int8
    decode through the fused tick."""
    q, pk, pv, ks, vs, tbl, lens, _ = _q8_inputs(seed=34)
    lens = jnp.maximum(lens, 1)
    q1 = q[:, :, :1]
    qls = jnp.ones(q.shape[1], jnp.int32)
    r = paged_attention_layers_ragged_q8(q1, pk, pv, ks, vs, tbl, lens,
                                         qls, force_pallas=True)
    d = paged_attention_q8(q1[0, :, 0], pk[0], pv[0], ks[0], vs[0], tbl,
                           lens, force_pallas=True)
    assert np.array_equal(np.asarray(r[0, :, 0]), np.asarray(d))
    per_layer = [paged_attention_ragged_q8(q1[l], pk[l], pv[l], ks[l],
                                           vs[l], tbl, lens, qls,
                                           force_pallas=True)
                 for l in range(q.shape[0])]
    assert np.array_equal(np.asarray(r), np.stack([np.asarray(x)
                                                   for x in per_layer]))


def test_q8_ragged_ignores_dead_pages():
    """Poisoning int8 slots AND their scale planes past each row's length
    must not change the int8 ragged output."""
    q, pk, pv, ks, vs, tbl, lens, qls = _q8_inputs(seed=35)
    out1 = paged_attention_layers_ragged_q8(q, pk, pv, ks, vs, tbl, lens,
                                            qls, force_pallas=True)
    pk2, pv2 = np.asarray(pk).copy(), np.asarray(pv).copy()
    ks2 = np.asarray(ks.astype(jnp.float32)).copy()
    vs2 = np.asarray(vs.astype(jnp.float32)).copy()
    T, MP = pk.shape[2], tbl.shape[1]
    tl = np.asarray(tbl)
    ln = np.asarray(lens)
    for b in range(tl.shape[0]):
        for lp in range(MP):
            phys, start = tl[b, lp], lp * T
            if start >= ln[b]:
                pk2[:, phys], pv2[:, phys] = 127, -127
                ks2[:, phys], vs2[:, phys] = 1e6, 1e6
            elif start + T > ln[b]:
                pk2[:, phys, ln[b] - start:] = 127
                pv2[:, phys, ln[b] - start:] = -127
                ks2[:, phys, ln[b] - start:] = 1e6
                vs2[:, phys, ln[b] - start:] = 1e6
    out2 = paged_attention_layers_ragged_q8(
        q, jnp.asarray(pk2), jnp.asarray(pv2),
        jnp.asarray(ks2, jnp.bfloat16), jnp.asarray(vs2, jnp.bfloat16),
        tbl, lens, qls, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


def test_mla_ragged_matches_oracle_and_pads_zero():
    """MLA ragged entries vs the pure-jnp oracle over the latent + rope-key
    planes, plus exact zeros in every padding query slot."""
    q_c, q_r, pc, pkr, tbl, lens, qls, scale = _mla_inputs()
    out = mla_paged_attention_layers_ragged(q_c, q_r, pc, pkr, tbl, lens,
                                            qls, scale=scale,
                                            force_pallas=True)
    ref = mla_paged_attention_layers_ragged_ref(q_c, q_r, pc, pkr, tbl,
                                                lens, qls, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=4e-5)
    o1 = mla_paged_attention_ragged(q_c[0], q_r[0], pc[0], pkr[0], tbl,
                                    lens, qls, scale=scale,
                                    force_pallas=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(out)[0],
                               atol=1e-4, rtol=4e-5)
    o = np.asarray(out)
    for b in range(o.shape[1]):
        assert np.all(o[:, b, int(qls[b]):] == 0.0), b


def test_mla_qlen1_is_bitwise_decode_entry():
    """q_len = 1 through the MLA ragged entries IS the MLA decode entry bit
    for bit, and the multi-layer launch is bitwise the stacked
    single-layer launches."""
    q_c, q_r, pc, pkr, tbl, lens, _, scale = _mla_inputs(seed=36)
    lens = jnp.maximum(lens, 1)
    qc1, qr1 = q_c[:, :, :1], q_r[:, :, :1]
    qls = jnp.ones(q_c.shape[1], jnp.int32)
    r = mla_paged_attention_layers_ragged(qc1, qr1, pc, pkr, tbl, lens,
                                          qls, scale=scale,
                                          force_pallas=True)
    d = mla_paged_attention(qc1[0, :, 0], qr1[0, :, 0], pc[0], pkr[0], tbl,
                            lens, scale=scale, force_pallas=True)
    assert np.array_equal(np.asarray(r[0, :, 0]), np.asarray(d))
    per_layer = [mla_paged_attention_ragged(qc1[l], qr1[l], pc[l], pkr[l],
                                            tbl, lens, qls, scale=scale,
                                            force_pallas=True)
                 for l in range(q_c.shape[0])]
    assert np.array_equal(np.asarray(r), np.stack([np.asarray(x)
                                                   for x in per_layer]))


def test_mla_ragged_ignores_dead_pages():
    """Poisoning latent AND rope-key slots past each row's length must not
    change the MLA ragged output."""
    q_c, q_r, pc, pkr, tbl, lens, qls, scale = _mla_inputs(seed=37)
    out1 = mla_paged_attention_layers_ragged(q_c, q_r, pc, pkr, tbl, lens,
                                             qls, scale=scale,
                                             force_pallas=True)
    pc2, pkr2 = np.asarray(pc).copy(), np.asarray(pkr).copy()
    T, MP = pc.shape[2], tbl.shape[1]
    tl, ln = np.asarray(tbl), np.asarray(lens)
    for b in range(tl.shape[0]):
        for lp in range(MP):
            phys, start = tl[b, lp], lp * T
            if start >= ln[b]:
                pc2[:, phys], pkr2[:, phys] = 1e6, -1e6
            elif start + T > ln[b]:
                pc2[:, phys, ln[b] - start:] = 1e6
                pkr2[:, phys, ln[b] - start:] = -1e6
    out2 = mla_paged_attention_layers_ragged(
        q_c, q_r, jnp.asarray(pc2), jnp.asarray(pkr2), tbl, lens, qls,
        scale=scale, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


# ------------------------------------------------------------------ log patch
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,T,C,N", [(5, 8, 16, 20), (3, 16, 128, 64),
                                     (2, 4, 8, 1)])
def test_log_patch_matches_oracle(P, T, C, N, dtype):
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal((P, T, C)), dtype)
    pays = jnp.asarray(rng.standard_normal((N, C)), dtype)
    pg = jnp.asarray(rng.integers(0, P, N), jnp.int32)
    sl = jnp.asarray(rng.integers(0, T, N), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, N), jnp.int32)
    out = log_patch(pool, pays, pg, sl, valid, force_pallas=True)
    ref = log_patch_ref(pool, pays, pg, sl, valid.astype(bool))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-6)


def test_log_patch_replay_order():
    """Later log records must win on slot collisions (replay semantics)."""
    pool = jnp.zeros((1, 4, 8), jnp.float32)
    pays = jnp.stack([jnp.full((8,), 1.0), jnp.full((8,), 2.0)])
    pg = jnp.zeros((2,), jnp.int32)
    sl = jnp.zeros((2,), jnp.int32)
    out = log_patch(pool, pays, pg, sl, force_pallas=True)
    assert float(out[0, 0, 0]) == 2.0
