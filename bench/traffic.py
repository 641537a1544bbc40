"""Request generator: one general generator over traffic-mix data files.

A mix (``bench/traffic/<mix>.json``) declares its arrival process and its
prompt and output length distributions; a cell (``bench/cells/<cell>.json``)
gives the offered rate where the loop is open. Every seed gets the same set
of sizes and the same set of inter-arrival gaps, and its own token ids:
lengths are the lognormal's quantiles at ``(i + 0.5) / n`` and gaps the
exponential's. So a run always holds the same work, and the programs it
compiles are the same for every seed.

The order of those sizes and gaps is drawn from ``order_seed`` where the
mix gives one, else from the run's seed. With ``order_seed`` every seed
replays one trace: the same requests at the same times, with its own token
ids (and, in ``bench/weights.py``, its own weights). On an open loop the
order decides which requests queue and how many rows each chunked prefill
runs beside, so a tail over a few dozen requests varies with the order far
more than with the system.

The order is balanced where the mix gives ``strata`` (k): each sorted set
is cut into k strata of adjacent quantiles, and each run of k consecutive
requests takes one value of every stratum, in a drawn order. So no trace
bunches the longest prompts, or the shortest gaps, into one stretch of the
window. ``strata`` 1 is a plain permutation.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

@dataclass
class Planned:
    """A request as the generator plans it. ``due`` is seconds after the
    window opens (None for a backlog, which is queued before the window)."""
    rid: int
    prompt: np.ndarray
    max_new: int
    due: float | None


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lognormal lengths at the quantiles ``(i + 0.5) / n``, with the
    declared median and sigma, rounded and clipped to ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the quantiles
    ``(i + 0.5) / n`` of a Poisson process at ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def balanced_order(rng, n: int, k: int) -> np.ndarray:
    """A permutation of ``range(n)``, indices into a sorted set, in which
    the ``b``-th run of ``k`` places holds the ``b``-th draw of each of
    ``k`` strata of adjacent indices, shuffled within the run. Strata
    differ in size by one at most; the last run may be short."""
    k = min(max(k, 1), n)
    bounds = [round(s * n / k) for s in range(k + 1)]
    strata = [rng.permutation(np.arange(bounds[s], bounds[s + 1]))
              for s in range(k)]
    order = []
    for b in range(max(len(s) for s in strata)):
        order.extend(rng.permutation([s[b] for s in strata if b < len(s)]))
    return np.asarray(order, dtype=np.int64)


def request_count(mix: dict, cell: dict, seconds: float) -> int:
    if mix["arrival"] == "backlog":
        return int(mix["requests"])
    if mix["arrival"] == "poisson":
        return max(int(round(cell["rate_rps"] * seconds)), 1)
    raise ValueError(f"unknown arrival process {mix['arrival']!r}")


def make_requests(mix: dict, cell: dict, seed: int, seconds: float,
                  vocab: int) -> list[Planned]:
    """The run's requests, in the order they are due (or queued)."""
    n = request_count(mix, cell, seconds)
    k = int(mix.get("strata", 1))
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix.get("order_seed", seed))
    prompts = quantile_lengths(mix["prompt"], n)[balanced_order(order, n, k)]
    outputs = quantile_lengths(mix["output"], n)[balanced_order(order, n, k)]
    if mix["arrival"] == "poisson":
        gaps = quantile_gaps(cell["rate_rps"], n)[balanced_order(order, n,
                                                                 k)]
        dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        dues = [None] * n
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
        out.append(Planned(rid=i, prompt=toks, max_new=int(outputs[i]),
                           due=None if dues[i] is None else float(dues[i])))
    return out


def first_chunks(requests: list[Planned], chunk: int) -> set[int]:
    """Admission prefill lengths the list reaches: the whole prompt when it
    is shorter than the chunk, else the chunk."""
    return {min(len(r.prompt), chunk) for r in requests}


def chunk_lengths(requests: list[Planned], chunk: int) -> set[int]:
    """Every ``q_len`` a row of the fused step can carry: 1 for decode, and
    each prompt-tail chunk after the admission prefill."""
    out = {1}
    for r in requests:
        rest = len(r.prompt) - min(len(r.prompt), chunk)
        while rest > 0:
            out.add(min(rest, chunk))
            rest -= chunk
    return out


def pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by the nearest-rank rule: the
    smallest value with at least ``q`` percent of the sample at or below
    it. Empty samples have none."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    k = max(math.ceil(q / 100.0 * len(vals)) - 1, 0)
    return float(vals[k])
