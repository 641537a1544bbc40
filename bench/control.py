"""Read a cell's check numbers for setting its limit, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: serve the cell at its own load for
``--seconds``, then read on the same sample of served requests the widest
gap of the program's tokens (the lower reading) and of the fp8 control's
first choices (``bench/reference.py``, ``quant="fp8"``: the upper reading).
Prints one JSON line per seed, with ``correct`` and ``control_correct``:
each judged by ``run.judge`` at the cell's committed limit. The limit in
``bench/cells/<cell>.json`` is set between the largest program reading and
the smallest control reading; the control has to come out not correct.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.traffic.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.Cell(spec, args.workload)
    run.enable_cache(run.CACHE_DIR)
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, control=True)
        print(json.dumps({"seed": seed, "program": res["checks"]
                          ["max_logit_gap"]["value"],
                          "correct": res["correct"],
                          "control": res["control"]["max_logit_gap"],
                          "control_correct": res["control"]["correct"],
                          "tokens": res["checks"]["tokens_checked"]["value"],
                          "metrics": res["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
