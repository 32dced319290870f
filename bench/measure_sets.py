#!/usr/bin/env python3
"""Run a cell several times and print how widely its metrics spread.

    python3 bench/measure_sets.py --workload <cell> --seeds 11 12 13 \
        [--sets 2] [--seconds <run_seconds>] [--trace 0|1] [--out <file.jsonl>]

Each run is a process of its own (`bench/run.py`); this parent never
touches JAX, so the chip is free for each child. A set is one run per
seed; every set uses the same seeds. For each metric it prints each
set's median and spread: the distance between the first and the third
quartile (`statistics.quantiles(values, n=4)`) as a share of the median.
The result lines go to `--out`, one JSON object a line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values: list[float]) -> float | None:
    if len(values) < 2 or not statistics.median(values):
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / abs(statistics.median(values))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    lines = []
    for s in range(args.sets):
        for seed in args.seeds:
            cmd = [*manifest["command"], "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(args.trace)]
            t0 = time.perf_counter()
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True)
            wall = time.perf_counter() - t0
            last = proc.stdout.strip().splitlines()[-1:] or [""]
            try:
                result = json.loads(last[0])
            except ValueError:
                result = {"correct": False, "metrics": {}}
                print(proc.stdout[-3000:], proc.stderr[-3000:], sep="\n")
            # the run's own lines too: what a far-off run did differently
            row = {"set": s, "seed": seed, "rc": proc.returncode,
                   "wall_s": wall, **result,
                   "log": proc.stdout.strip().splitlines()[:-1][-40:]}
            lines.append(row)
            print(f"set {s} seed {seed} rc {proc.returncode} wall "
                  f"{wall:.1f} s correct {result.get('correct')} "
                  + " ".join(f"{k}={v['value']:.6g}"
                             for k, v in result["metrics"].items()),
                  flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    names = sorted({k for row in lines for k in row["metrics"]})
    for name in names:
        for s in range(args.sets):
            vals = [row["metrics"][name]["value"] for row in lines
                    if row["set"] == s and name in row["metrics"]]
            # a side's first run compiles: setup_s is judged without it
            kept = vals[1:] if name == "setup_s" and s == 0 else vals
            sp = spread(kept)
            print(f"{name} set {s}: median {statistics.median(kept):.6g} "
                  f"spread {'n/a' if sp is None else f'{100 * sp:.3f}%'} "
                  f"n={len(kept)} values {[round(v, 4) for v in vals]}")
    bad = [row for row in lines if row["rc"] != 0 or not row.get("correct")]
    print(f"runs {len(lines)}, not correct or failed: {len(bad)}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
