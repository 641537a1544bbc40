"""Find an open-loop cell's knee once, on the chip: serve the cell's mix at
several fixed rates in one process and print, per rate, the tails and
whether the queue grew.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.5 1 2 4

A rate is sustained when the requests due in the window's last third wait
for their first token about as long as those of its first third, and the
queue at the close is about the running set. The cell's ``rate_rps`` is
set to about four fifths of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import run  # sets up the import paths

import harness  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = traffic.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.Cell(spec, args.workload)
    run.enable_cache(run.CACHE_DIR)
    if run.device_line()["platform"] != "tpu":
        raise SystemExit("the sweep measures the chip; JAX found no TPU")
    cfg = cell.cfg
    compiles = harness.CompileCounter()
    model, params = harness.build(cfg, args.seed)
    plans = {r: traffic.make_requests(cell.mix, {"rate_rps": r}, args.seed,
                                      args.seconds, cfg["vocab_size"])
             for r in args.rates}
    union = [p for plan in plans.values() for p in plan]
    for rate, planned in plans.items():
        engine, sched = harness.make_engine(model, params, cfg)
        harness.warm_up(engine, union, cfg)
        rec = harness.new_record(planned, args.seconds)
        clock = harness.Clock()
        harness.instrument(engine, rec, clock)
        harness.run_window(sched, rec, clock, compiles, {}, planned)
        r = run.Run(cell, rec, 0.0, {}, None)
        third = args.seconds / 3

        def ttft(lo, hi):
            waits = []
            for s in rec.served.values():
                if s.due is not None and lo <= s.due < hi:
                    t = [x for x in s.token_times if x <= rec.close]
                    waits.append((t[0] if t else rec.close) - s.due)
            return traffic.percentile(waits, 50) * 1e3 if waits else None

        print(json.dumps({
            "rate_rps": rate, "requests": len(planned),
            "ttft_p90_ms": run.load_reader("ttft_p90_ms")(r),
            "itl_p95_ms": run.load_reader("itl_p95_ms")(r),
            "ttft_p50_first_third_ms": ttft(0, third),
            "ttft_p50_last_third_ms": ttft(2 * third, args.seconds),
            "queued_at_close": len(sched.waiting) + len(sched.preempted),
            "running_at_close": len(sched.running),
            "ticks": len(rec.ticks),
            "compiles_in_window": rec.compiles_in_window}), flush=True)
        del engine, sched
    return 0


if __name__ == "__main__":
    sys.exit(main())
