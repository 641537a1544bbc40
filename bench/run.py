"""Run one benchmark cell once, in one process, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, rate and check limit are found by name under
``bench/``. The run makes its requests and weights from ``--seed``, warms
every program those requests reach (set-up), serves for ``--seconds``,
then checks the served tokens against the plain float32 reference (its
family's ``logits``, ``bench/families/<model_type>.py``) on a sample drawn
from the seed. It exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``, each number compared beside its
limit. The same numbers close standard error.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import families  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


class Cell:
    """A workload entry of ``BENCHMARK.json`` with its files resolved, its
    configuration's family module among them."""

    def __init__(self, spec: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in spec["configs"]}
        self.cfg = traffic.load_json(root / configs[self.entry["config"]]
                                     ["file"])
        bench = root / "bench"
        self.family = families.load(self.cfg, bench / "families")
        self.mix = traffic.load_json(bench / "traffic" /
                                     f"{self.entry['traffic']}.json")
        self.params = traffic.load_json(bench / "cells" / f"{name}.json")
        self.mode = "backlog" if self.mix["arrival"] == "backlog" \
            else "openloop"

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]


def load_reader(name: str, bench: Path = BENCH):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, else the
    file of the quantity the name splits by suffix
    (``mfu.backlog`` → ``mfu.py``)."""
    if str(bench / "metrics") not in sys.path:
        sys.path.insert(0, str(bench / "metrics"))
    for stem in (name, name.split(".")[0]):
        path = bench / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{bench / 'metrics'}")


def load_peaks(kind: str, bench: Path = BENCH) -> dict:
    table = traffic.load_json(bench / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def device_line() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def enable_cache(path: Path) -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, keeping every program however quick it was to compile."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pick_sample(reqs: dict, seed: int, want_tokens: int,
                max_requests: int, preempted: set) -> list:
    """Finished requests to check: the longest, one that was preempted and
    restored if any, then others in an order drawn from the seed, until
    ``want_tokens`` served tokens or ``max_requests``."""
    done = sorted((r for r in reqs.values() if r.done and r.generated),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.generated),
                                       -r.rid))
    out = [longest]
    spilled = [r for r in done if r.rid in preempted and r is not longest]
    rng = np.random.default_rng(seed)
    if spilled:
        out.append(spilled[int(rng.integers(len(spilled)))])
    rest = [r for r in done if all(r is not o for o in out)]
    for i in rng.permutation(len(rest)):
        if sum(len(r.generated) for r in out) >= want_tokens \
                or len(out) >= max_requests:
            break
        out.append(rest[int(i)])
    return out


def check_tokens(cfg: dict, seed: int, sample: list,
                 quant: str | None = None) -> tuple:
    """The widest gap, over every served token of ``sample``, by which the
    reference logit of the served token (or, with ``quant``, of the
    control's first choice) lies below the reference's best. Weights are
    made again from the seed. Returns ``(widest gap, tokens checked)``."""
    params = weights.make_params(cfg, seed)
    s = cfg["max_len"]
    fn = jax.jit(lambda p, t: reference.gaps(p, cfg, t, quant))
    widest, n = 0.0, 0
    for r in sample:
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.generated, np.int32)])
        toks = np.zeros(s, np.int32)
        toks[:len(seq)] = seq[:s]
        g = np.asarray(fn(params, jnp.asarray(toks)))
        p, m = len(r.prompt), len(r.generated)
        served = g[p - 1:p - 1 + m]
        if not np.all(np.isfinite(served)):
            return math.inf, n + m
        widest = max(widest, float(np.max(served)))
        n += m
    del params
    return widest, n


def judge(gap: float, n_tok: int, limit: float) -> bool:
    """The comparison that decides ``correct``: at least one served token
    checked, and the widest gap within the cell's limit."""
    return n_tok >= 1 and gap <= limit


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             program_cfg=None, require_tpu: bool = True,
             trace_dir: str | None = None, control: bool = False) -> dict:
    """Set up, serve, measure and check one run; returns the result dict
    (without printing). ``program_cfg`` and ``require_tpu=False`` are for
    tests at a reduced size on the CPU; ``control`` also judges the fp8
    control on the same sample by the same comparison and limit, as
    ``result["control"]``."""
    device = device_line()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell.entry["chips"]):
        raise SystemExit(f"{cell.name} needs {cell.entry['chips']} TPU "
                         f"chip(s); JAX found {device['count']} "
                         f"{device['platform']} device(s)")
    cfg = cell.cfg
    peaks = load_peaks(device["kind"]) if require_tpu else \
        load_peaks("TPU v5 lite")
    compiles = harness.CompileCounter()
    model, params = harness.build(cfg, seed, program_cfg)
    planned = traffic.make_requests(cell.mix, cell.params, seed, seconds,
                                    cfg["vocab_size"])
    engine, sched = harness.make_engine(model, params, cfg)
    harness.warm_up(engine, planned, cfg)
    rec = harness.new_record(planned, seconds)
    clock = harness.Clock()
    harness.instrument(engine, rec, clock)
    reqs = harness.fill_backlog(sched, planned) \
        if cell.mode == "backlog" else {}
    setup_s = time.perf_counter() - T_START
    print(f"set-up {setup_s:.3f} s, {compiles.programs} programs compiled "
          f"({compiles.cache_hits} from the persistent cache, "
          f"{compiles.seconds:.3f} s compiling)", file=sys.stderr,
          flush=True)
    own_dir = trace and trace_dir is None
    if trace:
        trace_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    rec.counters_open = dict(engine.stats())
    harness.run_window(sched, rec, clock, compiles, reqs,
                       planned if cell.mode == "openloop" else ())
    rec.counters_close = dict(engine.stats())
    if trace:
        jax.profiler.stop_trace()
    memory = peak_bytes()
    print(f"window {rec.close:.3f} s: {len(rec.ticks)} ticks, "
          f"{len(rec.steps)} fused steps, {len(rec.prefills)} admissions, "
          f"{rec.compiles_in_window} programs compiled and "
          f"{rec.cache_loads_in_window} loaded from the cache inside it",
          file=sys.stderr, flush=True)
    if rec.lateness:
        print(f"generator lateness: p50 "
              f"{traffic.percentile(rec.lateness, 50) * 1e3:.3f} ms, max "
              f"{max(rec.lateness) * 1e3:.3f} ms over {len(rec.lateness)} "
              f"requests", file=sys.stderr, flush=True)
    run = Run(cell, rec, setup_s, peaks, None)
    if trace:
        run.events = trace_reduce.load_events(trace_reduce.find_xplane(
            trace_dir))
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = trace_reduce.reduce(run.events)
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in names:
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    want = int(cell.params["check_tokens"])
    harness.finish_for_check(sched, reqs, want)
    sample = pick_sample(reqs, seed, want,
                         int(cell.params["check_max_requests"]),
                         rec.preempted)
    del sched, engine, params, model
    gc.collect()
    t0 = time.perf_counter()
    gap, n_tok = check_tokens(cfg, seed, sample)
    limit = float(cell.params["gap_limit"])
    print(f"reference check: {len(sample)} requests, {n_tok} served tokens "
          f"in {time.perf_counter() - t0:.3f} s", file=sys.stderr)
    correct = judge(gap, n_tok, limit)
    checks = {"max_logit_gap": {"value": gap if math.isfinite(gap) else None,
                                "limit": limit},
              "tokens_checked": {"value": n_tok, "limit": 1}}
    attempted = len(planned) if cell.mode == "openloop" else sum(
        1 for s in rec.served.values()
        if any(0 < t <= rec.close for t in s.token_times))
    dev = dict(device, memory_peak_bytes=memory)
    result = {"correct": bool(correct), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = run.trace["busy_ns"] / 1e9
        dev["window_s"] = run.trace["window_ns"] / 1e9
        result["breakdown"] = run.trace["breakdown"]
    if control:
        c_gap, c_tok = check_tokens(cfg, seed, sample, "fp8")
        result["control"] = {"max_logit_gap": c_gap,
                             "correct": judge(c_gap, c_tok, limit)}
    result["checks"] = checks
    return result


class Run:
    """What a metric reader reads: the cell, the record, the set-up time,
    the chip's peaks and, in a traced run, the reduced trace."""

    def __init__(self, cell: Cell, rec, setup_s: float, peaks: dict,
                 red: dict | None):
        self.cell, self.cfg, self.rec = cell, cell.cfg, rec
        self.setup_s, self.peaks, self.trace = setup_s, peaks, red
        self.events: list = []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    spec = traffic.load_json(ROOT / "BENCHMARK.json")
    cell = Cell(spec, args.workload)
    enable_cache(CACHE_DIR)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
