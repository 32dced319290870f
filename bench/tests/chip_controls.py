#!/usr/bin/env python3
"""The controls on the chip, at the cell's own size.

    python3 bench/tests/chip_controls.py --workload <cell> --seeds 1 2 3 [--seconds 8]

For each seed: the cell's own set-up, warm-up and a short window at its
own load; what the timed path produced has to compare correct; then each
control of `controls.py` (the reference in the program's place with one
guarantee broken) is compared in its stead and has to come out not
correct. Prints one line a reading and exits non-zero if a sound run
reads not correct or a control reads correct. The benchmark's own runs
never run this; `test_drivers_cpu.py` keeps the same controls at a size
a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import controls                       # noqa: E402
from bench import run as harness     # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.find_cell(manifest, args.workload)
    workload = harness.load_json(harness.BENCH, "workloads",
                                 cell["name"] + ".json")
    config = harness.load_json(harness.BENCH, "configs",
                               cell["config"] + ".json")
    driver = harness.load_module("drivers", config["driver"])
    device, _ = harness.gate_on_chip(cell["chips"])
    harness.enable_compile_cache()
    print("device: " + json.dumps(device), flush=True)

    bad = 0
    for seed in args.seeds:
        state = driver.setup(config, workload, seed, harness.log)
        try:
            driver.warm(state, harness.log)
            run = driver.window(state, args.seconds, lambda: None,
                                harness.log)
            observed = driver.observe(state, run)
        finally:
            driver.close(state, harness.log)
        sound = driver.compare(config, workload, observed)
        readings = {c["name"]: c["value"] for c in sound}
        ok = all(c["ok"] for c in sound)
        bad += not ok
        print(f"seed {seed} program: correct {ok} {json.dumps(readings)}",
              flush=True)
        if config["driver"] == "ecbench":
            made = controls.ecbench_controls(config, observed)
        else:
            made = controls.rados_controls(config, driver, observed)
        for what, control in made.items():
            checks = driver.compare(config, workload, control)
            readings = {c["name"]: c["value"] for c in checks if not c["ok"]}
            correct = all(c["ok"] for c in checks)
            wanted = what.startswith("_")       # the sound reference itself
            bad += correct != wanted
            print(f"seed {seed} control {what}: correct {correct} "
                  f"failed {json.dumps(readings)}", flush=True)
    print(f"chip_controls: {bad} reading(s) not as they have to be")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
