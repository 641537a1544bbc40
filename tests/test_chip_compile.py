"""Compile the served kernels and the fused serving step for a TPU v5e chip.

Nothing runs: the TPU compiler compiles for a described chip, with none
attached, at published widths. This catches what interpret mode cannot — a
block shape the Mosaic lowering refuses, a kernel that needs more VMEM than
the compiler allows, a step that does not fit the chip's HBM. The topology
is described inside a fixture (never at import), and the persistent
compilation cache is off around these compiles: an entry compiled for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.kernel import (
    mla_paged_attention_ragged_pallas, paged_attention_layers_ragged_pallas,
    paged_attention_layers_ragged_q8_pallas, paged_attention_ragged_pallas,
    paged_attention_ragged_q8_pallas)
from repro.models import build_model

V5E_HBM_BYTES = 16 * 10**9
# the served shapes chip_smoke.py reaches: batch-width bucket, the largest
# Qmax bucket (its prefill chunk), 16-token pages, a 1 GiB pool
BATCH, QMAX, PAGE_TOKENS, POOL_BYTES = 8, 256, 16, 1 << 30
MAX_PAGES = (1024 + 32) // PAGE_TOKENS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _ragged_args(one_chip, lead, qmax, int8):
    """(q, planes..., block_table, lengths, q_lens) at InternLM2-1.8B widths
    over a 1 GiB pool's page count."""
    cfg = get_config("internlm2-1.8b")
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pages = POOL_BYTES // (cfg.num_layers * PAGE_TOKENS * K * D * 2 * 2)
    kv = jnp.int8 if int8 else jnp.bfloat16
    planes = [_spec(one_chip, lead + (pages, PAGE_TOKENS, K, D), kv)] * 2
    if int8:
        planes += [_spec(one_chip, lead + (pages, PAGE_TOKENS, K),
                         jnp.bfloat16)] * 2
    i32 = jnp.int32
    return ([_spec(one_chip, lead + (BATCH, qmax, H, D), jnp.bfloat16)]
            + planes + [_spec(one_chip, (BATCH, MAX_PAGES), i32),
                        _spec(one_chip, (BATCH,), i32),
                        _spec(one_chip, (BATCH,), i32)])


@pytest.mark.parametrize("entry,lead,int8", [
    (paged_attention_ragged_pallas, (), False),
    (paged_attention_ragged_q8_pallas, (), True),
    (paged_attention_layers_ragged_pallas, (4,), False),
    (paged_attention_layers_ragged_q8_pallas, (4,), True),
], ids=["dense", "int8", "dense-layers", "int8-layers"])
@pytest.mark.parametrize("qmax", [1, QMAX])
def test_gqa_ragged_kernel_compiles_for_v5e(one_chip, entry, lead, int8,
                                            qmax):
    compiled = _compile(entry, *_ragged_args(one_chip, lead, qmax, int8))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("qmax", [1, 16])
def test_mla_ragged_kernel_compiles_for_v5e(one_chip, qmax):
    cfg = get_config("deepseek-v2-236b")
    H, dc, dr = cfg.num_heads, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    pages, i32, bf = 680, jnp.int32, jnp.bfloat16
    args = [_spec(one_chip, (BATCH, qmax, H, dc), bf),
            _spec(one_chip, (BATCH, qmax, H, dr), bf),
            _spec(one_chip, (pages, PAGE_TOKENS, dc), bf),
            _spec(one_chip, (pages, PAGE_TOKENS, dr), bf),
            _spec(one_chip, (BATCH, MAX_PAGES), i32),
            _spec(one_chip, (BATCH,), i32), _spec(one_chip, (BATCH,), i32)]
    scale = 1.0 / (cfg.mla.qk_nope_head_dim + dr) ** 0.5
    compiled = _compile(
        lambda *a: mla_paged_attention_ragged_pallas(*a, scale=scale), *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("qmax", [1, QMAX])
def test_fused_step_compiles_and_fits_v5e(one_chip, monkeypatch, qmax):
    """``LM.step_paged_ragged`` for InternLM2-1.8B in bf16 over a 1 GiB
    pool: the Pallas kernel is in the program, and two steps fit HBM.
    ``memory_analysis`` bounds one step only (arguments, outputs and
    temporaries). Served on the chip, the same run peaks at about twice
    that, since no jit donates the pool; until the peak is attributed and
    the pool donated, the test asks room for two steps in flight.
    The kernel entries pick Pallas by ``jax.default_backend()``, which sees
    the CPU here, so the test steers them to the TPU branch."""
    cfg = get_config("internlm2-1.8b")
    model = build_model(cfg, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16, remat=False)
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    desc = model.cache_descriptor(PAGE_TOKENS)
    pages = POOL_BYTES // desc.page_group_bytes
    cache = {"block_table": _spec(one_chip, (BATCH, MAX_PAGES), jnp.int32)}
    for p in desc.paged_planes:
        cache["pool_" + p.name] = _spec(
            one_chip, (cfg.num_layers, pages, PAGE_TOKENS) + tuple(p.shape),
            p.np_dtype)
    i32 = jnp.int32
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(model.step_paged_ragged, params, cache,
                        _spec(one_chip, (BATCH, qmax), i32),
                        _spec(one_chip, (BATCH,), i32),
                        _spec(one_chip, (BATCH,), i32))
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 2 * live <= V5E_HBM_BYTES, (
        f"two steps of {live} bytes do not fit one v5e chip")
