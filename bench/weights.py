"""Random weights from the seed, made by the benchmark and not the program.

The program takes its parameters as a pytree; this module states that tree
for the dense GQA family (names and shapes) from a configuration file, and
fills it on the device in one jitted call: matrices from
N(0, initializer_range), norm scales from 1 + N(0, 0.1), every leaf from its
own key folded from the seed. The reference calls the same function again
after the program's state is freed, so it takes nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
NORM_STD = 0.1


def padded_vocab(cfg: dict, multiple: int = 256) -> int:
    """The program allocates its embedding tables padded to a multiple of
    256 rows; the padded rows are never looked up and their logits are
    masked."""
    v = cfg["vocab_size"]
    return -(-v // multiple) * multiple


def layout(cfg: dict) -> dict:
    """``{path: shape}`` of the parameter tree, path as a tuple of keys."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    n_layers = cfg["num_hidden_layers"]
    vp = padded_vocab(cfg)
    out = {("embed", "table"): (vp, d), ("final_ln", "scale"): (d,)}
    if not cfg["tie_word_embeddings"]:
        out[("head", "table")] = (vp, d)
    per_layer = {
        ("ln_attn", "scale"): (d,), ("ln_ffn", "scale"): (d,),
        ("attn", "wq"): (d, h * dh), ("attn", "wk"): (d, k * dh),
        ("attn", "wv"): (d, k * dh), ("attn", "wo"): (h * dh, d),
        ("ffn", "w_gate"): (d, f), ("ffn", "w_up"): (d, f),
        ("ffn", "w_down"): (f, d),
    }
    for path, shape in per_layer.items():
        out[("blocks",) + path] = (n_layers,) + shape
    return out


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


def make_params(cfg: dict, seed: int):
    """The whole parameter tree on the default device, in the served dtype,
    from one jitted call."""
    shapes = layout(cfg)
    dtype = DTYPES[cfg["param_dtype"]]
    std = float(cfg["initializer_range"])

    def build(key):
        flat = {}
        for i, (path, shape) in enumerate(sorted(shapes.items())):
            sub = jax.random.fold_in(key, i)
            z = jax.random.normal(sub, shape, jnp.float32)
            leaf = 1.0 + NORM_STD * z if path[-1] == "scale" else std * z
            flat[path] = leaf.astype(dtype)
        return _nest(flat)

    return jax.jit(build)(seed_key(seed))


def check_against(params_shape, cfg: dict) -> None:
    """Fail unless the program's own parameter tree (``jax.eval_shape`` of
    its init) has exactly this layout's paths and shapes."""
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shape)[0]:
        flat[tuple(p.key for p in path)] = tuple(leaf.shape)
    want = {p: tuple(s) for p, s in layout(cfg).items()}
    if flat != want:
        missing = sorted(set(want) - set(flat))
        extra = sorted(set(flat) - set(want))
        differ = sorted(p for p in set(want) & set(flat)
                        if want[p] != flat[p])
        raise RuntimeError(
            f"the program's parameter tree differs from the benchmark's "
            f"layout for {cfg['name']}: missing {missing}, extra {extra}, "
            f"other shapes {[(p, flat[p], want[p]) for p in differ]}")
