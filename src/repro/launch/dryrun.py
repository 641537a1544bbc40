import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input-shape × mesh) cell against the production mesh and
record memory / cost / collective artifacts for the roofline analysis.

The lines above MUST stay first: jax locks the device count on first
init, and only the dry-run wants 512 host devices (tests/benches see 1).
The dry-run emulates its mesh on host devices, so it pins the CPU platform:
a dry-run process never claims an accelerator, and the children that
``benchmarks/roofline_bench.py`` starts cannot contend for a chip that
their parent holds.

Usage:
    python -m repro.launch.dryrun --arch starcoder2-15b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
    python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --layers 2
        (--layers overrides depth for the roofline L-differencing compiles)
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES_BY_NAME, applicable_shapes,
                           get_config, skipped_shapes)
from repro.distributed import batch_specs, cache_specs, data_axes, param_specs
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (abstract_cache, abstract_params,
                                abstract_state, input_specs, state_specs,
                                with_shardings)
from repro.models import build_model
from repro.roofline.hlo import collective_summary
from repro.roofline.hw import V5E
from repro.training.optimizer import AdamWConfig
from repro.training.step import make_train_step

ACTIVATION_BUDGET = 4e9        # bytes/device of scan-carried residuals


def pick_microbatches(cfg, shape, mesh) -> int:
    dp = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    if cfg.family in ("ssm", "hybrid"):
        dp *= mesh.shape.get("model", 1)     # model axis folded into batch
    b_loc = max(shape.global_batch // dp, 1)
    layers = cfg.num_layers + cfg.num_encoder_layers
    act = b_loc * shape.seq_len * cfg.d_model * 2 * max(layers, 1)
    mb = 1
    while act / mb > ACTIVATION_BUDGET and mb < b_loc:
        mb *= 2
    return mb


def moment_dtype_for(cfg):
    # 100B+ models need bf16 moments to fit v5e HBM (EXPERIMENTS.md math)
    return jnp.bfloat16 if cfg.param_count() > 60e9 else jnp.float32


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               layers_override=None, chunk_size: int = 512,
               mb_override=None, period_override=None,
               unroll: bool = False, kv_cache_dtype: str = "native"):
    cfg = get_config(arch)
    if layers_override:
        cfg = dataclasses.replace(cfg, num_layers=layers_override,
                                  num_encoder_layers=min(
                                      cfg.num_encoder_layers, layers_override))
        if cfg.hybrid is not None:
            period = period_override or max(layers_override // 2, 1)
            cfg = dataclasses.replace(cfg, hybrid=dataclasses.replace(
                cfg.hybrid, shared_block_period=period))
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shape.kind

    ep = data_axes(mesh) if cfg.moe is not None else ()
    if kind == "train":
        model = build_model(cfg, param_dtype=jnp.bfloat16,
                            compute_dtype=jnp.bfloat16, remat=True,
                            chunk_size=chunk_size, ep_axes=ep,
                            scan_unroll=unroll)
        mb = mb_override or pick_microbatches(cfg, shape, mesh)
        state_shape = abstract_state(model, moment_dtype_for(cfg))
        sspecs = state_specs(state_shape, cfg, mesh)
        state_in = with_shardings(state_shape, sspecs, mesh)
        batch_shape = input_specs(cfg, shape, microbatches=mb)
        # SSM/hybrid backbones have no TP mapping for the mixer weights —
        # fold the model axis into batch so all 256 chips carry batch.
        # Pick the largest axis combination that divides the global batch
        # (multi-pod: 512 ∤ 256 → fall back to data×model).
        dp_override = None
        if cfg.family in ("ssm", "hybrid"):
            da = data_axes(mesh)
            for cand in (da + ("model",), ("data", "model"), da, ("data",)):
                cand = tuple(a for a in cand if a in mesh.axis_names)
                size = int(np.prod([mesh.shape[a] for a in cand]))
                if cand and shape.global_batch % size == 0:
                    dp_override = cand
                    break
        bspecs = batch_specs(batch_shape, mesh, microbatched=mb > 1,
                             dp_override=dp_override)
        batch_in = with_shardings(batch_shape, bspecs, mesh)
        accum_dtype = (jnp.bfloat16 if cfg.param_count() > 60e9
                       else jnp.float32)
        step = make_train_step(model, AdamWConfig(), microbatches=mb,
                               accum_dtype=accum_dtype)
        with use_mesh(mesh):
            lowered = jax.jit(
                step,
                out_shardings=(jax.tree.map(
                    lambda s: NamedSharding(mesh, s), sspecs,
                    is_leaf=lambda x: isinstance(x, P)), None),
            ).lower(state_in, batch_in)
        return lowered, {"microbatches": mb, "kind": kind}

    model = build_model(cfg, param_dtype=jnp.bfloat16,
                        compute_dtype=jnp.bfloat16, remat=False,
                        chunk_size=chunk_size, ep_axes=ep,
                        scan_unroll=unroll,
                        kv_cache_dtype=kv_cache_dtype)
    params_shape = abstract_params(model)
    pspecs = param_specs(params_shape, cfg, mesh)
    params_in = with_shardings(params_shape, pspecs, mesh)

    if kind == "prefill":
        batch_shape = input_specs(cfg, shape)
        bspecs = batch_specs(batch_shape, mesh)
        batch_in = with_shardings(batch_shape, bspecs, mesh)
        with use_mesh(mesh):
            lowered = jax.jit(
                lambda p, b: model.prefill(p, b, shape.seq_len)
            ).lower(params_in, batch_in)
        return lowered, {"kind": kind}

    # decode: one token against an S-token cache
    with use_mesh(mesh):
        cache_shape = abstract_cache(model, cfg, shape)
    cspecs = cache_specs(cache_shape, cfg, mesh)
    cache_in = with_shardings(cache_shape, cspecs, mesh)
    batch_shape = input_specs(cfg, shape)
    bspecs = batch_specs(batch_shape, mesh)
    batch_in = with_shardings(batch_shape, bspecs, mesh)
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    pos_spec = P(dp) if shape.global_batch % dp_size == 0 else P()
    pos_in = jax.ShapeDtypeStruct(
        (shape.global_batch,), jnp.int32,
        sharding=NamedSharding(mesh, pos_spec))
    with use_mesh(mesh):
        lowered = jax.jit(
            model.decode_step,
            out_shardings=(None, jax.tree.map(
                lambda s: NamedSharding(mesh, s), cspecs,
                is_leaf=lambda x: isinstance(x, P))),
        ).lower(params_in, cache_in, batch_in["tokens"], pos_in)
    return lowered, {"kind": kind}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             layers_override=None, keep_hlo: bool = False,
             mb_override=None, period_override=None,
             unroll: bool = False, kv_cache_dtype: str = "native",
             chunk_size: int = 512) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    if layers_override:
        tag += f"__L{layers_override}"
    if period_override:
        tag += f"P{period_override}"
    if kv_cache_dtype != "native":
        tag += f"__kv{kv_cache_dtype}"
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, multi_pod, layers_override,
                               mb_override=mb_override,
                               period_override=period_override,
                               unroll=unroll, kv_cache_dtype=kv_cache_dtype,
                               chunk_size=chunk_size)
    meta["kv_cache_dtype"] = kv_cache_dtype
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = collective_summary(hlo)
    n_dev = len(jax.devices())
    mem = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
        "output_bytes": getattr(ma, "output_size_in_bytes", None),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes",
                                        None),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
    }
    live = (mem["argument_bytes"] or 0) + (mem["temp_bytes"] or 0)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "devices": n_dev, **meta,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "per_device_live_bytes": live,
        "fits_v5e_hbm": bool(live <= V5E.hbm_bytes),
        "cost": {k: v for k, v in ca.items()
                 if "flops" in k or k == "bytes accessed"},
        "collectives": colls,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{tag}.json").write_text(json.dumps(result, indent=1))
    if keep_hlo:
        (out_dir / f"{tag}.hlo.txt").write_text(hlo)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--layers", type=int, default=None,
                    help="override depth (roofline L-differencing)")
    ap.add_argument("--mb", type=int, default=None,
                    help="override train microbatch count")
    ap.add_argument("--period", type=int, default=None,
                    help="override hybrid shared-block period (roofline)")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll every scan so cost_analysis counts all "
                         "work (roofline sample compiles)")
    ap.add_argument("--kv-dtype", default="native",
                    choices=("native", "int8"),
                    help="KV-cache dtype for decode cells (§Perf C)")
    ap.add_argument("--chunk", type=int, default=512,
                    help="attention chunk size (samples use 2048 to "
                         "bound unrolled-body count)")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args(argv)

    out_dir = Path(args.out)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([SHAPES_BY_NAME[args.shape]] if args.shape
                  else applicable_shapes(cfg))
        for sh in shapes:
            for mp in meshes:
                tag = f"{arch} × {sh.name} × {'multipod' if mp else 'pod'}"
                try:
                    r = run_cell(arch, sh.name, mp, out_dir,
                                 layers_override=args.layers,
                                 keep_hlo=args.keep_hlo,
                                 mb_override=args.mb,
                                 period_override=args.period,
                                 unroll=args.unroll,
                                 kv_cache_dtype=args.kv_dtype,
                                 chunk_size=args.chunk)
                    print(f"[ok] {tag}: live={r['per_device_live_bytes']/1e9:.2f}GB"
                          f" fits={r['fits_v5e_hbm']}"
                          f" colls={r['collectives'].get('num_ops', 0)}"
                          f" compile={r['compile_s']}s", flush=True)
                except Exception as e:
                    failures += 1
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}",
                          flush=True)
                    traceback.print_exc()
        for sh_name, reason in (skipped_shapes(cfg) if not args.shape else []):
            print(f"[skip] {arch} × {sh_name}: {reason}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
