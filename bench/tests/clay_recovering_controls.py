#!/usr/bin/env python3
"""The controls of the `rados_recovering_clay` driver: what the program
produced, with one guarantee of `rados_clay_k8m4d11_13osd_1out` broken.
Each has to come out as not correct. Used by
`test_rados_recovering_clay_cpu.py` (a small size, the CPU) and, run as
a command, on the chip at the cell's own size:

    python3 bench/tests/clay_recovering_controls.py --workload <cell> --seeds 1 2 [--seconds 8]

For each seed: the cell's own set-up, warm-up and a short window at its
own load, then recovery to its end; what the run produced has to compare
correct; then each control (a wrong byte in a rebuilt row under a crc
taken of it, an acknowledged write missing, an object rebuilt from whole
rows, a recovery that ended before the close) is compared in its stead
and has to come out not correct. Prints one line a reading and exits
non-zero if a sound run reads not correct or a control reads correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench.reference import clay_codeword     # noqa: E402


def clay_recovering_controls(config: dict, ob: dict) -> dict[str, dict]:
    """`ob` as the driver's `observe` gives it, sound. Every control is
    it with one thing broken."""
    g = config["geometry"]
    lost_slot = ob["map"]["lost_slot"]
    rebuilt = next(i for i, o in enumerate(ob["objects"])
                   if o["origin"] == "backlog"
                   and lost_slot[o["pg"]] is not None)
    acked = next(i for i, o in enumerate(ob["objects"])
                 if o["origin"] == "window")

    def bent():
        # the repair rebuilt a wrong byte, and the crc was taken of it:
        # only the definition of the codeword can tell
        objects = list(ob["objects"])
        o = objects[rebuilt]
        slot = lost_slot[o["pg"]]
        row = np.array(o["rows"][slot])
        row[-1] ^= 1
        rows, crcs = list(o["rows"]), list(o["crcs"])
        rows[slot], crcs[slot] = row, int(clay_codeword.crcs(row[None])[0])
        objects[rebuilt] = dict(o, rows=rows, crcs=crcs)
        return objects

    since, rec = ob["since_failure"], ob["recovery"]
    row = g["object_bytes"] // g["k"]
    slices = list(rec["rebuilt_by_slice"])
    return {
        # a wrong byte in the row the PG lost, on its new member
        "wrong_rebuilt_row_on_the_new_member": dict(ob, objects=bent()),
        # a write the window acknowledged is nowhere
        "acknowledged_write_missing": dict(
            ob, objects=[o for i, o in enumerate(ob["objects"])
                         if i != acked]),
        # every object pulled k whole rows, as an RS pool's repair does
        "rebuilt_from_whole_rows": dict(ob, since_failure=dict(
            since, recover_wire_bytes=since["recovered_objects"]
            * g["k"] * row)),
        # the backlog was whole before the window closed
        "recovery_ended_before_the_close": dict(ob, recovery=dict(
            rec, rebuilt_by_slice=slices[:-1] + [0],
            rebuilt_at_close=since["recovered_objects"])),
        # what the program produced, as it was: has to pass
        "_sound": ob,
    }


def main() -> int:
    from bench import run as harness
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
            driver.finish(state, run, harness.log)
            observed = driver.observe(state, run)
        finally:
            driver.close(state, harness.log)
        for what, control in clay_recovering_controls(config,
                                                      observed).items():
            checks = driver.compare(config, workload, control)
            failed = {c["name"]: c["value"] for c in checks if not c["ok"]}
            correct = not failed
            bad += correct != what.startswith("_")
            print(f"seed {seed} {what}: correct {correct} failed "
                  f"{json.dumps(failed)}", flush=True)
    print(f"clay_recovering_controls: {bad} reading(s) not as they have "
          f"to be")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
