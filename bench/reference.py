"""The plain float32 reference, its fp8 control, and the gaps that decide
``correct``.

Each family's forward pass is ``logits`` in ``bench/families/<model_type>.py``
(chosen by the configuration's ``model_type``), written from the published
architecture and importing nothing of the program. It builds on the
primitives here, so the control means the same thing for every family:
matrix products run at ``highest`` precision, so float32 is float32 on the
TPU too, and ``quant="fp8"`` rounds every matrix product's operands to
float8 e4m3, weights scaled per output column and activations per row, as
an fp8 serving path would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import families

F32 = jnp.float32
E4M3_MAX = 448.0


def fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def matmul(x, w, quant):
    """``x @ w``, with both operands rounded to fp8 where ``quant`` is
    ``"fp8"``."""
    if quant == "fp8":
        x, w = fp8(x, -1), fp8(w, 0)
    return x @ w


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
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
    ``(S,)``, causal, by the configuration's family."""
    return families.load(cfg).logits(params, cfg, tokens, quant)


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
