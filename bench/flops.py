"""Analytic operations and bytes of the served work, from ragged shapes.

Counts come from each call's real rows, ``(ctx_len, q_len)``: ``ctx_len``
tokens already in the KV pool and ``q_len`` new tokens. Padding (bucketed
rows, query slots past ``q_len``, dead page slots) counts for nothing, so
the count is the same whatever implements the work. A multiply-add is two
operations. The configuration dict is a ``bench/configs`` file.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, h, k, d // h, cfg["num_hidden_layers"]


def matmul_params(cfg: dict) -> int:
    """Weights each new token multiplies through the decoder stack."""
    d, h, k, dh, n_layers = _dims(cfg)
    per_layer = d * h * dh + 2 * d * k * dh + h * dh * d \
        + 3 * d * cfg["intermediate_size"]
    return n_layers * per_layer


def causal_pairs(ctx: int, q: int) -> int:
    """(query, key) pairs of ``q`` new tokens after ``ctx`` cached ones:
    query ``j`` attends ``ctx + j + 1`` keys."""
    return q * ctx + q * (q + 1) // 2


def attention_flops(cfg: dict, ctx: int, q: int) -> int:
    """One layer's attention operations for one row: scores and the
    weighted sum, ``4 * head_dim`` per (query, key) pair and query head."""
    _, h, _, dh, _ = _dims(cfg)
    return 4 * h * dh * causal_pairs(ctx, q)


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
    tf = fl / peaks["bf16_flops_per_s"]
    tb = by / peaks["hbm_bytes_per_s"]
    return max(tf, tb), tf, tb
