"""Analytic operations and bytes of the served work, from ragged shapes.

Counts come from each call's real rows, ``(ctx_len, q_len)``: ``ctx_len``
tokens already in the KV pool and ``q_len`` new tokens. Padding (bucketed
rows, query slots past ``q_len``, dead page slots) counts for nothing, so
the count is the same whatever implements the work. A multiply-add is two
operations. The configuration dict is a ``bench/configs`` file; what a
step and a kernel call do is its family's to count
(``bench/families/<model_type>.py``).
"""
from __future__ import annotations

import families


def causal_pairs(ctx: int, q: int) -> int:
    """(query, key) pairs of ``q`` new tokens after ``ctx`` cached ones:
    query ``j`` attends ``ctx + j + 1`` keys."""
    return q * ctx + q * (q + 1) // 2


def least_seconds(ops: int, nbytes: int, peaks: dict) -> tuple:
    """The least time of work of ``ops`` operations and ``nbytes`` bytes of
    HBM traffic: the larger of operations over the bf16 peak and bytes
    over bandwidth. Returns ``(seconds, flops_seconds, bytes_seconds)``."""
    tf = ops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return max(tf, tb), tf, tb


def step_flops(cfg: dict, rows) -> int:
    """Useful operations of one fused step over ``rows`` of
    ``(ctx_len, q_len)``."""
    return families.load(cfg).step_flops(cfg, rows)


def kernel_least_seconds(cfg: dict, rows, peaks: dict) -> tuple:
    """The least time one layer's paged-attention call could take over
    ``rows``: ``(seconds, flops_seconds, bytes_seconds)``."""
    return families.load(cfg).kernel_least_seconds(cfg, rows, peaks)
