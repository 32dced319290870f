"""The controls: the plain reference put in the program's place, with one
guarantee of the configuration broken. Each has to come out as not
correct. Used by the tests here (tiny size, CPU) and by `chip_controls.py`
(the cell's own size, on the chip)."""

from __future__ import annotations

import numpy as np

from bench.reference import crc32c, gf256


def ecbench_controls(config: dict, observed: list[dict]) -> dict[str, list]:
    g = config["geometry"]
    other = gf256.cauchy_orig(g["k"], g["m"])
    return {
        # parity of another technique under the same k and m: the byte
        # format the profile states is broken
        "cauchy_orig_parity": [
            dict(o, parity=gf256.rs_encode(other, o["data"]))
            for o in observed],
    }


def rados_controls(config: dict, drv, ob: dict) -> dict[str, dict]:
    """`ob` as `drv.observe` gives it; each control rebuilds the stored
    objects from the reference and breaks one guarantee."""
    g = config["geometry"]
    right = gf256.reed_sol_van(g["k"], g["m"])
    other = gf256.cauchy_orig(g["k"], g["m"])
    n = g["k"] + g["m"]
    payloads = ob["payloads"]

    def rebuilt(matrix, seed=drv.CRC_SEED, drop=None, stale=False):
        made = {}
        for pay in {o["payload"] for o in ob["objects"]}:
            rows = drv.reference_rows(config, payloads[pay], matrix)
            made[pay] = (list(rows),
                         [int(c) for c in crc32c.crc32c_rows(seed, rows)])
        objects = []
        for o in ob["objects"]:
            rows, crcs = (list(x) for x in made[o["payload"]])
            if drop is not None:
                rows[drop], crcs[drop] = None, None
            o = dict(o, rows=rows, crcs=crcs)
            if "readback" in o:
                back = payloads[o["payload"]]
                o["readback"] = back[::-1] if stale else back
            objects.append(o)
        return objects

    out = {
        # stored parity and crcs of another technique
        "cauchy_orig_parity": dict(ob, objects=rebuilt(other)),
        # hinfo crc from seed 0, not Ceph's -1
        "crc_seed_zero": dict(ob, objects=rebuilt(right, seed=0)),
        # acked with one of the k+m shards not committed
        "ack_before_last_shard": dict(ob, objects=rebuilt(right, drop=n - 1)),
        # a read that returns other bytes than were written
        "stale_read": dict(
            ob, objects=rebuilt(right, stale=True),
            reads=[dict(r, returned=payloads[r["payload"]][::-1])
                   for r in ob["reads"]]),
    }
    # the sound reference itself has to pass, or the controls prove nothing
    out["_sound_reference"] = dict(ob, objects=rebuilt(right))
    return out
