#!/usr/bin/env python3
"""The controls of the `rados_recovering` driver: the plain reference put
in the program's place, with one guarantee of `rados_k8m3_12osd_1out`
broken. Each has to come out as not correct. Used by
`test_rados_recovering_cpu.py` (a tiny size, the CPU) and, run as a
command, on the chip at the cell's own size (`rbd_controls.py`'s way):

    python3 bench/tests/recovering_controls.py --workload <cell> --seeds 1 2 [--seconds 8]

For each seed: the cell's own set-up, warm-up and a short window at its
own load, then recovery to its end; what the run produced has to compare
correct; then each control (a wrong rebuilt row on the new member, an
acknowledged write missing, two PGs on a target at once, a grant over
the budget, a recovery that ended before the close) is compared in its
stead and has to come out not correct. Prints one line a reading and exits non-zero if a sound run
reads not correct or a control reads correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench.reference import crc32c, recovered_pool     # noqa: E402


def recovering_controls(config: dict, drv, ob: dict) -> dict[str, dict]:
    """`ob` as `drv.observe` gives it. Every control holds what the
    reference says the new acting set has to store, with one thing
    broken."""
    g = config["geometry"]
    want = recovered_pool.stripes(ob["payloads"], g["k"], g["m"],
                                  g["stripe_unit_bytes"])

    def objects(alter=None, drop=None):
        out = []
        for i, o in enumerate(ob["objects"]):
            if i == drop:
                continue
            rows, crcs = want[o["payload"]]
            o = dict(o, rows=list(rows), crcs=[int(c) for c in crcs])
            if "readback" in o:
                o["readback"] = ob["payloads"][o["payload"]]
            if alter is not None:
                alter(i, o)
            out.append(o)
        return out

    lost_slot = ob["map"]["lost_slot"]
    # a backlog object of a PG that lost a slot, and a write the window
    # acknowledged
    rebuilt = next(i for i, o in enumerate(ob["objects"])
                   if o["origin"] == "backlog"
                   and lost_slot[o["pg"]] is not None)
    acked = next(i for i, o in enumerate(ob["objects"])
                 if o["origin"] == "window")

    def bent(i, o):
        # the decode rebuilt a wrong byte, and the crc was taken of it:
        # only the plain decode of the survivors' rows can tell
        if i == rebuilt:
            slot = lost_slot[o["pg"]]
            row = np.array(o["rows"][slot])
            row[-1] ^= 1
            o["rows"][slot] = row
            o["crcs"][slot] = int(crc32c.crc32c_rows(
                recovered_pool.CRC_SEED, row[None, :])[0])

    rec, gauges = ob["recovery"], ob["gauges"]
    budget = (config["recovery"]["osd_recovery_max_active"]
              * config["recovery"]["osd_recovery_max_chunk"])
    slices = list(rec["rebuilt_by_slice"])
    out = {
        # a wrong byte in the row the PG lost, on its new member
        "wrong_rebuilt_row_on_the_new_member": dict(
            ob, objects=objects(bent)),
        # a write the window acknowledged is nowhere
        "acknowledged_write_missing": dict(ob, objects=objects(drop=acked)),
        # a target took pushes for two PGs at once
        "two_pgs_on_a_target_at_once": dict(
            ob, objects=objects(), gauges=dict(
                gauges, backfills_active_max=config["recovery"][
                    "osd_max_backfills"] + 1)),
        # a grant staged the old 32 objects
        "grant_over_the_budget": dict(
            ob, objects=objects(), gauges=dict(
                gauges, recover_grant_bytes_max=budget + g["object_bytes"])),
        # the backlog was whole before the window closed: its last slice
        # saw no recovery and nothing was left at the close
        "recovery_ended_before_the_close": dict(
            ob, objects=objects(), recovery=dict(
                rec, rebuilt_by_slice=slices[:-1] + [0],
                rebuilt_at_close=ob["since_failure"]["recovered_objects"])),
    }
    # the sound reference itself has to pass, or the controls prove nothing
    out["_sound_reference"] = dict(ob, objects=objects())
    return out


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
        sound = driver.compare(config, workload, observed)
        ok = all(c["ok"] for c in sound)
        bad += not ok
        print(f"seed {seed} program: correct {ok} "
              f"{json.dumps({c['name']: c['value'] for c in sound})}",
              flush=True)
        for what, control in recovering_controls(config, driver,
                                                 observed).items():
            checks = driver.compare(config, workload, control)
            readings = {c["name"]: c["value"] for c in checks if not c["ok"]}
            correct = all(c["ok"] for c in checks)
            wanted = what.startswith("_")       # the sound reference itself
            bad += correct != wanted
            print(f"seed {seed} control {what}: correct {correct} "
                  f"failed {json.dumps(readings)}", flush=True)
    print(f"recovering_controls: {bad} reading(s) not as they have to be")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
