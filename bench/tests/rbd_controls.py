#!/usr/bin/env python3
"""The controls of the `rbd_bench` driver: the block-device reference put
in the program's place, with one guarantee of `rbd_ec_k8m3_12osd` broken.
Each has to come out as not correct. Used by `test_rbd_bench_cpu.py` (a
tiny size, the CPU) and, run as a command, on the chip at the cell's own
size (`chip_controls.py`'s way):

    python3 bench/tests/rbd_controls.py --workload <cell> --seeds 1 2 [--seconds 8]

For each seed: the cell's own set-up, warm-up and a short window at its
own load; what the timed path produced has to compare correct; then each
control is compared in its stead and has to come out not correct. Prints
one line a reading and exits non-zero if a sound run reads not correct or
a control reads correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench.reference import gf256     # noqa: E402


def rbd_controls(config: dict, drv, ob: dict) -> dict[str, dict]:
    """`ob` as `drv.observe` gives it. Every control holds what the
    reference would have stored and returned after `ob`'s history, with
    one thing broken."""
    g, block = config["geometry"], config["image"]["block_bytes"]
    k = g["k"]
    matrix = gf256.reed_sol_van(g["k"], g["m"])
    image, _ = drv.reference_image(config, ob)
    rows, crcs = drv.reference_shards(config, image, matrix)

    def objects(rows=rows, crcs=crcs):
        return [dict(o, readback=image[i].tobytes(), rows=list(rows[i]),
                     crcs=[int(c) for c in crcs[i]])
                for i, o in enumerate(ob["objects"])]

    # the write acknowledged last, and the image as it was before it
    last = max((w for w in ob["history"] if w["ok"]), key=lambda w: w["end"])
    before, _ = drv.reference_image(
        config, dict(ob, history=[w for w in ob["history"] if w is not last]))
    o = last["object"]
    old_rows, old_crcs = drv.reference_shards(config, before[o:o + 1],
                                              matrix)
    col = drv.column_of(config, last["offset"])

    # one more write was acknowledged than the device holds: a block of
    # object 0 whose bytes differ from what is there
    n_pay = len(ob["payloads"])
    at = max(w["end"] for w in ob["history"]) + 1.0
    dropped = next(
        w for w in ({"object": 0, "offset": 0, "payload": p, "cut": cut,
                     "start": at, "end": at + 0.01, "ok": True}
                    for p in range(n_pay) for cut in (0, block))
        if ob["payloads"][w["payload"]][w["cut"]:w["cut"] + block]
        != image[0, :block].tobytes())

    stale_parity = [list(r) for r in rows], [list(c) for c in crcs]
    stale_parity[0][o][k:] = list(old_rows[0][k:])
    stale_parity[1][o][k:] = list(old_crcs[0][k:])
    stale_crc = [list(c) for c in crcs]
    stale_crc[o][col] = old_crcs[0][col]
    c = ob["counters"]
    out = {
        # an acknowledged write that is not in the image
        "acked_write_dropped": dict(ob, objects=objects(),
                                    history=ob["history"] + [dropped]),
        # the last write's data applied, the parity rows left as they were
        "parity_left_stale": dict(ob, objects=objects(*stale_parity)),
        # the last write's data shard keeps the hinfo crc of the old row
        "stale_hinfo_crc": dict(ob, objects=objects(crcs=stale_crc)),
        # one write laddered to the full-stripe path: 11 shards moved
        "one_write_full_path": dict(ob, objects=objects(), counters=dict(
            c, rmw_full_fallbacks=c["rmw_full_fallbacks"] + 1,
            rmw_ops=c["rmw_ops"] - 1,
            rmw_shard_ios=c["rmw_shard_ios"] - (1 + g["m"]))),
        # an intent logged on a shard and never applied or dropped
        "journal_intent_left": dict(ob, objects=objects(), journal=dict(
            ob["journal"], intents_left=[[ob["objects"][o]["pg"], col,
                                          "e%016x" % 1]])),
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
            observed = driver.observe(state, run)
        finally:
            driver.close(state, harness.log)
        sound = driver.compare(config, workload, observed)
        ok = all(c["ok"] for c in sound)
        bad += not ok
        print(f"seed {seed} program: correct {ok} "
              f"{json.dumps({c['name']: c['value'] for c in sound})}",
              flush=True)
        for what, control in rbd_controls(config, driver, observed).items():
            checks = driver.compare(config, workload, control)
            readings = {c["name"]: c["value"] for c in checks if not c["ok"]}
            correct = all(c["ok"] for c in checks)
            wanted = what.startswith("_")       # the sound reference itself
            bad += correct != wanted
            print(f"seed {seed} control {what}: correct {correct} "
                  f"failed {json.dumps(readings)}", flush=True)
    print(f"rbd_controls: {bad} reading(s) not as they have to be")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
