"""The dense GQA decoder family: InternLM2, and Llama-style blocks with
MiniCPM's scalings.

Written from the published architectures, importing nothing of the
program. Per layer: RMSNorm, rotary attention with grouped KV heads (query
head ``h`` reads KV head ``h // (H / K)``, rotate-half RoPE), a residual
branch scaled by ``scale_depth / sqrt(L)``, RMSNorm and a SwiGLU FFN on the
same scaled residual. The token embedding is multiplied by ``scale_emb``;
after a final RMSNorm the output head (the tied embedding divided by
``hidden / dim_model_base`` for MiniCPM) gives the logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import flops
from reference import F32, matmul, rms, rope
from weights import padded_vocab


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, h, k, d // h, cfg["num_hidden_layers"]


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


def logits(params, cfg: dict, tokens, quant: str | None = None):
    """Logits ``(S, vocab_size)`` float32 at every position of ``tokens``
    ``(S,)``, causal. Trailing padding tokens change no earlier position.
    Weights arrive in bfloat16 and are widened one layer at a time inside
    the layer scan, so the reference fits beside nothing else on one
    chip."""
    d = cfg["hidden_size"]
    n_h, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, n_layers = d // n_h, cfg["num_hidden_layers"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    res = cfg["scale_depth"] / n_layers ** 0.5 if "scale_depth" in cfg \
        else 1.0
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    group = n_h // n_kv

    def layer(h, lp):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        a = rms(h, lp["ln_attn"]["scale"], eps)
        q = matmul(a, lp["attn"]["wq"], quant).reshape(s, n_h, dh)
        k = matmul(a, lp["attn"]["wk"], quant).reshape(s, n_kv, dh)
        v = matmul(a, lp["attn"]["wv"], quant).reshape(s, n_kv, dh)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / (dh ** 0.5)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        h = h + res * matmul(o.reshape(s, n_h * dh), lp["attn"]["wo"],
                             quant)
        a = rms(h, lp["ln_ffn"]["scale"], eps)
        g = jax.nn.silu(matmul(a, lp["ffn"]["w_gate"], quant))
        u = matmul(a, lp["ffn"]["w_up"], quant)
        return h + res * matmul(g * u, lp["ffn"]["w_down"], quant), None

    vocab = cfg["vocab_size"]
    emb = params["embed"]["table"][:vocab]
    with jax.default_matmul_precision("highest"):
        h = emb[tokens].astype(F32) * cfg.get("scale_emb", 1.0)
        h, _ = jax.lax.scan(layer, h, params["blocks"])
        h = rms(h, params["final_ln"]["scale"].astype(F32), eps)
        if "dim_model_base" in cfg:
            h = h / (d / cfg["dim_model_base"])
        head = emb if cfg["tie_word_embeddings"] \
            else params["head"]["table"][:vocab]
        return matmul(h, head.astype(F32).T, quant)


def matmul_params(cfg: dict) -> int:
    """Weights each new token multiplies through the decoder stack."""
    d, h, k, dh, n_layers = _dims(cfg)
    per_layer = d * h * dh + 2 * d * k * dh + h * dh * d \
        + 3 * d * cfg["intermediate_size"]
    return n_layers * per_layer


def attention_flops(cfg: dict, ctx: int, q: int) -> int:
    """One layer's attention operations for one row: scores and the
    weighted sum, ``4 * head_dim`` per (query, key) pair and query head."""
    _, h, _, dh, _ = _dims(cfg)
    return 4 * h * dh * flops.causal_pairs(ctx, q)


def attention_bytes(cfg: dict, ctx: int, q: int, kv_bytes: int = 2,
                    act_bytes: int = 2) -> int:
    """One layer's least HBM traffic of attention for one row: every K and
    V token of the row read once, the queries read and the output
    written once."""
    _, h, k, dh, _ = _dims(cfg)
    return (ctx + q) * 2 * k * dh * kv_bytes + 2 * q * h * dh * act_bytes


def step_flops(cfg: dict, rows) -> int:
    """Useful operations of one fused step over ``rows`` of
    ``(ctx_len, q_len)``: every new token through every layer's matrices
    and attention, and one output-head row of logits per row (the logits
    of its last token, which the next token is chosen from)."""
    d, *_ = _dims(cfg)
    n_layers = cfg["num_hidden_layers"]
    out = 0
    for ctx, q in rows:
        if q <= 0:
            continue
        out += 2 * matmul_params(cfg) * q
        out += n_layers * attention_flops(cfg, ctx, q)
        out += 2 * d * cfg["vocab_size"]
    return out


def kernel_least_seconds(cfg: dict, rows, peaks: dict) -> tuple:
    """The least time one layer's ``paged_attention_ragged`` call could take
    over ``rows``: the larger of operations over peak and bytes over
    bandwidth. Returns ``(seconds, flops_seconds, bytes_seconds)``."""
    fl = sum(attention_flops(cfg, c, q) for c, q in rows if q > 0)
    by = sum(attention_bytes(cfg, c, q) for c, q in rows if q > 0)
    return flops.least_seconds(fl, by, peaks)


def program_fields(cfg: dict) -> dict:
    """The program's registry entry at this configuration: its widths
    exactly, its scales to a relative 1e-9."""
    n = cfg["num_hidden_layers"]
    return {
        "d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "num_layers": n, "vocab_size": cfg["vocab_size"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "embedding_scale": float(cfg.get("scale_emb", 1.0)),
        "residual_scale": cfg["scale_depth"] / n ** 0.5
        if "scale_depth" in cfg else 1.0,
        "logit_scale": cfg["dim_model_base"] / cfg["hidden_size"]
        if "dim_model_base" in cfg else 1.0,
    }


def small(cfg: dict) -> tuple:
    """The CPU-size twin: d 128, FFN 256, 4 heads of 32 over 2 KV heads,
    2 layers, a 512-token vocabulary."""
    return ({"hidden_size": 128, "intermediate_size": 256,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "num_hidden_layers": 2, "vocab_size": 512},
            {"num_kv_heads": 2})
