"""Logical→physical sharding rules for every architecture family.

Axis roles (DESIGN.md §5):

* ``pod`` + ``data``  — gradient/data parallel (batch), ZeRO-1 optimizer state
* ``model``           — TP (attention heads / ffn hidden / vocab), EP (MoE
                        experts), and KV-sequence parallelism for decode caches

SSM mixer weights are replicated on ``model`` (Mamba TP via head-sharded
in_proj splits is a recorded future hillclimb; the mixers are ≤2.6 GB in bf16,
see DESIGN.md §Arch-applicability); SSM decode *states* shard heads over
``model``.

Vocab dims that are not divisible by the axis size (minicpm 122753, seamless
256206) rely on GSPMD's uneven-sharding padding.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import (AbstractMesh, AxisType, Mesh, NamedSharding,
                          PartitionSpec as P)

MODEL_AXIS = "model"


# Every sharding call in the repo goes through these helpers, so the JAX
# surface it depends on is named in one place.

def make_abstract_mesh(shape, axes) -> AbstractMesh:
    """An ``AbstractMesh`` with ``shape``/``axes``, no devices attached."""
    return AbstractMesh(tuple(shape), tuple(axes))


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis of ``Auto`` type."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def get_abstract_mesh():
    """The mesh of the current context (set via :func:`use_mesh`)."""
    return jax.sharding.get_abstract_mesh()


def use_mesh(mesh: Mesh):
    """Context manager activating ``mesh``."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` over ``mesh``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def data_axes(mesh: Mesh) -> tuple:
    """The gradient-parallel axes present in this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _path_names(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(f"[{p.idx}]")
        else:
            out.append(str(p))
    return out


# parameter tensors whose *last* dim is a TP output (columns sharded)
_COL_SHARDED = {"wq", "wk", "wv", "w_gate", "w_up", "w_uq", "w_dq"}
# tensors whose second-to-last dim is a TP input (rows sharded)
_ROW_SHARDED = {"wo", "w_down"}
_REPLICATED = {"router", "scale", "conv_w", "conv_b", "a_log", "dt_bias",
               "d_skip", "norm_scale", "in_proj", "out_proj", "w_kr", "a", "b"}


def _param_spec(names: list[str], leaf, dp=()) -> P:
    ndim = np.ndim(leaf)
    name = names[-1]
    in_experts = "experts" in names
    if name in _REPLICATED:
        return P()
    if in_experts and name in ("w_gate", "w_up", "w_down"):
        # (L?, E, d, f): experts (dim -3) over model; FSDP-shard the expert
        # hidden dim over the data axes — 100B+ MoE weights cannot fit
        # model-parallel-only (EXPERIMENTS.md §Dry-run memory math)
        spec = [None] * ndim
        spec[ndim - 3] = MODEL_AXIS
        if dp:
            f_dim = ndim - 1 if name in ("w_gate", "w_up") else ndim - 2
            spec[f_dim] = dp
        return P(*spec)
    if name == "table":
        # embedding/lm-head (V, d): shard vocab
        spec = [None] * ndim
        spec[ndim - 2] = MODEL_AXIS
        return P(*spec)
    if name in ("w_uk", "w_uv"):
        # (L?, dc, H, dn): shard heads
        spec = [None] * ndim
        spec[ndim - 2] = MODEL_AXIS
        return P(*spec)
    if name in _COL_SHARDED:
        spec = [None] * ndim
        spec[ndim - 1] = MODEL_AXIS
        return P(*spec)
    if name in _ROW_SHARDED:
        spec = [None] * ndim
        spec[ndim - 2] = MODEL_AXIS
        return P(*spec)
    if "projector" in names and ndim >= 2:
        spec = [None] * ndim
        spec[ndim - 1] = MODEL_AXIS
        return P(*spec)
    return P()


def _axes_of(entry) -> tuple:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _drop_indivisible(spec: P, shape, mesh: Mesh) -> P:
    """Replace spec entries whose mesh-axis product does not divide the dim
    (uneven GSPMD shardings are rejected for jit outputs) with None."""
    if mesh is None:
        return spec
    out = []
    for i, entry in enumerate(list(spec) + [None] * (len(shape) - len(spec))):
        axes = _axes_of(entry)
        if not axes:
            out.append(None)
            continue
        size = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(entry if shape[i] % size == 0 else None)
    return P(*out)


def param_specs(params_shape: Any, cfg, mesh: Mesh = None) -> Any:
    """PartitionSpec pytree for a param pytree (shapes or arrays)."""
    dp = data_axes(mesh) if mesh is not None else ()

    def fn(path, leaf):
        spec = _param_spec(_path_names(path), leaf, dp)
        shape = getattr(leaf, "shape", ())
        return _drop_indivisible(spec, shape, mesh) if shape else spec
    return jax.tree_util.tree_map_with_path(fn, params_shape)


def zero1_specs(params_shape: Any, cfg, mesh: Mesh) -> Any:
    """ZeRO-1: optimizer-state spec = param spec + data axes on the first
    evenly-divisible unsharded dim (falls back to the param spec)."""
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def fn(path, leaf):
        base = _param_spec(_path_names(path), leaf, dp)
        shape = leaf.shape if hasattr(leaf, "shape") else ()
        base = _drop_indivisible(base, shape, mesh) if shape else base
        if dp_size <= 1 or not shape:
            return base
        spec = list(base) + [None] * (len(shape) - len(base))
        used = {a for s in spec for a in _axes_of(s)}
        if used & set(dp):
            return P(*spec)      # FSDP'd tensors are already dp-sharded
        for i, dim in enumerate(shape):
            if spec[i] is None and dim % dp_size == 0:
                spec[i] = dp
                return P(*spec)
        return P(*spec)
    return jax.tree_util.tree_map_with_path(fn, params_shape)


def batch_specs(batch_shape: Any, mesh: Mesh,
                microbatched: bool = False, dp_override=None) -> Any:
    """Shard the global-batch dim of every batch leaf over dp (dim 0, or
    dim 1 when the pipeline delivers microbatched (mb, B/mb, ...) leaves).

    ``dp_override`` widens the batch axes — SSM/hybrid train cells fold the
    otherwise-idle ``model`` axis into data parallelism (DESIGN.md §5)."""
    dp = dp_override if dp_override is not None else data_axes(mesh)

    def fn(leaf):
        shape = getattr(leaf, "shape", ())
        nd = len(shape)
        if not nd:
            return P()
        if microbatched:
            spec = P(None, dp, *([None] * (nd - 2)))
        else:
            spec = P(dp, *([None] * (nd - 1)))
        return _drop_indivisible(spec, shape, mesh)
    return jax.tree_util.tree_map(fn, batch_shape)


def cache_specs(cache_shape: Any, cfg, mesh: Mesh) -> Any:
    """Decode-cache shardings: batch over dp; KV sequence over ``model``
    (flash-decoding LSE merge); SSM state heads over ``model``."""
    dp = data_axes(mesh)
    mo = MODEL_AXIS

    def fn(path, leaf):
        names = _path_names(path)
        name = names[-1]
        nd = len(leaf.shape)
        if name == "pos":
            spec = P(dp)
        elif name in ("k", "v", "shared_k", "shared_v", "ek", "ev"):
            # (L, B, S, K, D) — sequence-shard over model
            spec = P(None, dp, mo, None, None)
        elif name in ("k_scale", "v_scale"):
            # (L, B, S, K)
            spec = P(None, dp, mo, None)
        elif name in ("c", "kr"):
            # MLA latents (L, B, S, dc)
            spec = P(None, dp, mo, None)
        elif name in ("ssm", "seg_ssm", "tail_ssm"):
            # (..., B, H, P, N): shard heads over model
            s = [None] * nd
            s[nd - 4] = dp
            s[nd - 3] = mo
            spec = P(*s)
        elif name in ("conv", "seg_conv", "tail_conv"):
            # (..., B, d_conv-1, conv_dim)
            s = [None] * nd
            s[nd - 3] = dp
            spec = P(*s)
        else:
            spec = P()
        return _drop_indivisible(spec, leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(fn, cache_shape)


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
