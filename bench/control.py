#!/usr/bin/env python3
"""Readings the correctness limits are set from, for one cell.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 2

For each seed, one run of the cell as ``bench/run.py`` makes it (a short
window), in one process so that set-up compiles once.  Each prints the
program's compared numbers and, read on the same served positions, the
control's: the float32 reference computed with every matmul operand in
float8, the precision below the bfloat16 the configurations serve in, put
in the program's place and judged by the cell's limits.  The lower reading
of a number is the largest the program gives over the seeds; the upper is
the smallest the control gives.  Exits non-zero where the program comes
out not correct, or the control correct, on any seed.  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell = bench_run.cell_spec(args.workload, bench)
    bench_run.enable_cache()
    low, high, sound = {}, {}, True
    for seed in (int(s) for s in args.seeds.split(",")):
        r = bench_run.run_cell(cell, seed, args.seconds, False, bench=bench,
                               control=True)
        c = r["control"]
        got = {k: v["value"] for k, v in r["checks"].items()}
        got.update(c["program"])
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": c["correct"],
                          "program": got, "control": c["readings"],
                          "memory_peak_bytes":
                              r["device"]["memory_peak_bytes"]}),
              flush=True)
        sound = sound and r["correct"] and not c["correct"]
        for k, v in got.items():
            low[k] = max(low.get(k, v), v)
        for k, v in c["readings"].items():
            high[k] = min(high.get(k, v), v)
    print(json.dumps({"lower": low, "control_upper": high}), flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
