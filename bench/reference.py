"""Plain float32 reference of the dense GQA decoder, and its fp8 control.

Written from the published architectures (Llama-style blocks; InternLM2 and
MiniCPM configurations), importing nothing of the program: token embedding
(times ``scale_emb``), then per layer RMSNorm, rotary attention with
grouped KV heads (query head ``h`` reads KV head ``h // (H / K)``,
rotate-half RoPE), a residual branch scaled by ``scale_depth / sqrt(L)``,
RMSNorm and a SwiGLU FFN on the same scaled residual; a final RMSNorm and
the output head (the tied embedding divided by ``hidden / dim_model_base``
for MiniCPM). Matrix products run at ``highest`` precision, so float32 is
float32 on the TPU too. Weights arrive in bfloat16 and are widened one
layer at a time inside the layer scan, so the reference fits beside
nothing else on one chip.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3, weights scaled per output column and activations
per row, as an fp8 serving path would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _matmul(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """Rotate-half RoPE over ``x: (S, heads, D)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[:, None].astype(F32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def logits(params, cfg: dict, tokens, quant: str | None = None):
    """Logits ``(S, vocab_size)`` float32 at every position of ``tokens``
    ``(S,)``, causal. Trailing padding tokens change no earlier position."""
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
        a = _rms(h, lp["ln_attn"]["scale"], eps)
        q = _matmul(a, lp["attn"]["wq"], quant).reshape(s, n_h, dh)
        k = _matmul(a, lp["attn"]["wk"], quant).reshape(s, n_kv, dh)
        v = _matmul(a, lp["attn"]["wv"], quant).reshape(s, n_kv, dh)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / (dh ** 0.5)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        h = h + res * _matmul(o.reshape(s, n_h * dh), lp["attn"]["wo"],
                              quant)
        a = _rms(h, lp["ln_ffn"]["scale"], eps)
        g = jax.nn.silu(_matmul(a, lp["ffn"]["w_gate"], quant))
        u = _matmul(a, lp["ffn"]["w_up"], quant)
        return h + res * _matmul(g * u, lp["ffn"]["w_down"], quant), None

    vocab = cfg["vocab_size"]
    emb = params["embed"]["table"][:vocab]
    with jax.default_matmul_precision("highest"):
        h = emb[tokens].astype(F32) * cfg.get("scale_emb", 1.0)
        h, _ = jax.lax.scan(layer, h, params["blocks"])
        h = _rms(h, params["final_ln"]["scale"].astype(F32), eps)
        if "dim_model_base" in cfg:
            h = h / (d / cfg["dim_model_base"])
        head = emb if cfg["tie_word_embeddings"] \
            else params["head"]["table"][:vocab]
        return _matmul(h, head.astype(F32).T, quant)


def gaps(params, cfg: dict, tokens, quant: str | None = None):
    """For every position ``j`` of ``tokens`` ``(S,)``: how far the
    reference logit of ``tokens[j + 1]`` lies below the reference's best
    (program check), and, with ``quant``, how far the reference logit of
    the control's first choice does. Returns ``(S - 1,)`` float32."""
    ref = logits(params, cfg, tokens)[:-1]
    best = jnp.max(ref, -1)
    if quant is None:
        pick = tokens[1:]
    else:
        pick = jnp.argmax(logits(params, cfg, tokens, quant)[:-1], -1)
    return best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
