"""``bench/flops.py`` and the dense family's counts against hand counts,
and every configuration's useful work never above what the program's
compiled step computes (so no share passes 1)."""
import json

import jax
import jax.numpy as jnp
import pytest

import families
import flops
import run

CFG = json.loads((run.BENCH / "configs" / "internlm2-1.8b.json").read_text())
DENSE = families.load(CFG)
PEAKS = run.load_peaks("TPU v5 lite")
SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((run.ROOT / c["file"]).read_text())
           for c in SPEC["configs"]}


def test_matmul_params_by_hand():
    d, f, h, k, dh, n = 2048, 8192, 16, 8, 128, 24
    per_layer = d * h * dh + 2 * d * k * dh + h * dh * d + 3 * d * f
    assert DENSE.matmul_params(CFG) == n * per_layer == 1_509_949_440


def test_causal_pairs_by_hand():
    assert flops.causal_pairs(0, 1) == 1
    assert flops.causal_pairs(10, 1) == 11
    assert flops.causal_pairs(0, 3) == 1 + 2 + 3
    assert flops.causal_pairs(5, 3) == 6 + 7 + 8


def test_step_flops_by_hand():
    rows = [(100, 1), (0, 4), (7, 0)]
    d, v, n, h, dh = 2048, 92544, 24, 16, 128
    want = 2 * DENSE.matmul_params(CFG) * 5 + 2 * 2 * d * v \
        + n * 4 * h * dh * (101 + 10)
    assert flops.step_flops(CFG, rows) == want


def test_kernel_bytes_and_bound_by_hand():
    rows = [(1023, 1)] * 8
    by = 8 * (1024 * 2 * 8 * 128 * 2 + 2 * 16 * 128 * 2)
    assert sum(DENSE.attention_bytes(CFG, c, q) for c, q in rows) == by
    least, tf, tb = flops.kernel_least_seconds(CFG, rows, PEAKS)
    assert least == tb == by / PEAKS["hbm_bytes_per_s"] and tf < tb
    big = [(0, 256)] * 8
    least, tf, tb = flops.kernel_least_seconds(CFG, big, PEAKS)
    assert least == max(tf, tb)


@pytest.mark.parametrize("rows", [[(40, 1), (3, 1)], [(0, 16), (20, 5)],
                                  [(17, 8), (0, 1), (30, 1)]])
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_useful_work_within_the_compiled_step(config, rows):
    """At the family's CPU size, the analytic count of a fused step's real
    rows stays at or below the compiled step's own operation count (XLA's
    cost analysis, layers unrolled): the share of any peak that the count
    makes can only err low. The cache is the program's own paged planes."""
    from repro.configs import get_config
    from repro.core.engines.desc import descriptor_for
    from repro.models import build_model

    cfg = CONFIGS[config]
    widths, reduced = families.load(cfg).small(cfg)
    cfg = dict(cfg, **widths)
    pc = get_config(cfg["program_arch"]).reduced(**reduced)
    model = build_model(pc, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32, remat=False,
                        scan_unroll=True)
    params = jax.eval_shape(model.init, jax.random.key(0))
    b = 1 << (len(rows) - 1).bit_length()
    qm = 1 << (max(q for _, q in rows) - 1).bit_length()
    pages, t = 8, 16
    desc = descriptor_for(pc, compute_dtype="float32", page_tokens=t)
    cache = {"block_table": jax.ShapeDtypeStruct((b, pages), jnp.int32)}
    for plane in desc.paged_planes:
        cache["pool_" + plane.name] = jax.ShapeDtypeStruct(
            (desc.num_layers, b * pages, t) + tuple(plane.shape),
            plane.np_dtype)
    vec = jax.ShapeDtypeStruct((b,), jnp.int32)
    lowered = jax.jit(model.step_paged_ragged).lower(
        params, cache, jax.ShapeDtypeStruct((b, qm), jnp.int32), vec, vec)
    cost = lowered.compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert 0 < flops.step_flops(cfg, rows) <= cost["flops"]
