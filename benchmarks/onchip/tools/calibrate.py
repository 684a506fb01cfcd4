"""Readings that the limits of a cell's check are set from, on the chip.

    python3 benchmarks/onchip/tools/calibrate.py --workload dsc33b.backlog \
        --seconds 10 --seeds 11 12 13 ... [--control-seeds 3]

In one process, for each seed: set up and serve one window exactly as a
benchmark run does, then read the check's numbers for the program and,
on the first ``--control-seeds`` seeds, for the control (the reference
in int8 in the program's place) and for a served token changed to
token + 1.  One JSON line per seed on standard output.  The benchmark's
own runs never compute the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    a = ap.parse_args()
    _, cell, device, _ = run.start(a.workload)
    from harness import stats
    from harness.check_run import check
    print(json.dumps({"device": device}), flush=True)
    for i, seed in enumerate(a.seeds):
        t = time.monotonic()
        r = run.serve_once(cell, seed, a.seconds, t_process=t)
        numbers, info = check(cell, r["params"], r["dims"], seed,
                              r["finished"], r["prompts"], r["flight"],
                              run.HERE, control=i < a.control_seeds)
        print(json.dumps({
            "seed": seed, "program": numbers, "info": info,
            "output_tokens_per_s": stats.output_tokens_per_s(r["window"]),
            "tokens_per_row_round": stats.tokens_per_row_round(r["window"]),
            "setup_s": r["setup_s"], "compiles": r["compiles"],
            "memory_peak_bytes": r["memory_peak_bytes"],
            "preemptions": r["preemptions"], "kv_held": r["kv_held"]}),
              flush=True)
        del r
        gc.collect()


if __name__ == "__main__":
    main()
