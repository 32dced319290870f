"""Driver `rados_recovering_clay`: `rados_recovering`'s run on a Clay
pool, which rebuilds a lost row from a sub-chunk range of each of its d
helpers instead of k whole rows.

How it reuses the others: this file imports `bench.drivers.rados` and
`bench.drivers.rados_recovering` and takes from them, unchanged, the
data and names, the boot, the backlog, the writer loops, the failure
carried through to the out mark, the window's place on the recovery's
timeline, recovery's counters sampled while the window runs, the pool's
state after it and what is read back (`rados_recovering.observe`), and
the checks of the recovery's state ((c) to (g) of the configuration's
guarantees). Nothing of either is patched. What it adds is what the code
changes:

* shapes: a rebuilt object pulls d ranges of row / q bytes (the repair
  planes), so a grant of `osd_recovery_max_active` x
  `osd_recovery_max_chunk` holds more objects, and the readers count an
  object by those bytes;
* set-up: before the boot, one object of the cell's size is written
  through a throw-away backend over in-memory stores, so that the write
  program is built before a client op can wait on one (all daemons
  share the process's program caches); a program that takes more than
  the one fused launch for it is refused there;
* the window: `recover_range_bytes_served` (the sources' shipped range
  bytes) sampled beside `recovered_objects`, where the program has it;
* the comparison: every object's k+m rows held to
  `bench/reference/clay_codeword.py` (the data rows the object striped,
  the parity check of the uncoupled planes), its hinfo crcs to those
  rows', and guarantee (h): every rebuilt object went through a range
  plan, d x row / q helper bytes on the wire for each, exactly.
"""

from __future__ import annotations

import time

import numpy as np

from bench.checks import check
from bench.drivers import rados, rados_recovering
from bench.reference import clay_codeword

RANGE_COUNTER = "recover_range_bytes_served"
# the checks of rados_recovering that hold the rows to RS, or a grant to
# RS's size: this file holds them instead
REPLACED = {"stored_rows_wrong", "stored_crcs_wrong", "shards_missing",
            "window_writes_missing", "window_objects_compared",
            "readback_wrong", "objects_read_back", "rebuilt_rows_wrong",
            "rebuilt_crcs_wrong", "rebuilt_rows_compared",
            "rebuilt_over_the_launches_room"}

warm = rados_recovering.warm
observe = rados_recovering.observe
finish = rados_recovering.finish
close = rados_recovering.close


# -- shapes -------------------------------------------------------------

def _range_row(config: dict) -> int:
    """Bytes one helper ships for one object: row / q of its row."""
    g = config["geometry"]
    q = g["d"] - g["k"] + 1
    return (g["object_bytes"] // g["k"]) // q


def helper_bytes_an_object(config: dict) -> int:
    return config["geometry"]["d"] * _range_row(config)


def work_bytes(config: dict, workload: dict, n_ops: float) -> float:
    """A client write: k+m rows and their crc words (`rados.work_bytes`)."""
    return rados.work_bytes(config, workload, n_ops)


def recovery_work_bytes(config: dict, objects_rebuilt: float) -> float:
    """Bytes the algorithm must move through device memory to rebuild
    that many objects: d helper ranges into the repair, the rebuilt row
    and its crc word out. From shapes alone."""
    row = config["geometry"]["object_bytes"] // config["geometry"]["k"]
    return float(objects_rebuilt) * (helper_bytes_an_object(config)
                                     + row + 4)


def grant_objects(config: dict) -> int:
    """Objects one grant may stage: the power of two under
    osd_recovery_max_active x osd_recovery_max_chunk over an object's
    helper bytes."""
    r = config["recovery"]
    fit = (r["osd_recovery_max_active"] * r["osd_recovery_max_chunk"]
           ) // helper_bytes_an_object(config)
    return 1 << max(0, fit.bit_length() - 1)


# -- set-up -------------------------------------------------------------

def setup(config: dict, workload: dict, seed: int, log) -> dict:
    from ceph_tpu.osd.ecbackend import ec_perf_counters
    state = rados_recovering.setup(config, workload, seed, log)
    state["range_counter"] = RANGE_COUNTER in ec_perf_counters().dump()
    _build_write_programs(config, log)
    return state


def _build_write_programs(config: dict, log) -> None:
    """One object of the cell's size written through a throw-away
    backend over in-memory stores: the write program is compiled here
    and not under a client op. Refuses a program that writes a Clay
    object in more than the one fused launch: such a program also
    builds its pool's programs inside the window, where a compile can
    stall the one backfill stream for a whole slice (guarantee (c))."""
    from ceph_tpu.osd.ecbackend import ECBackend
    g = config["geometry"]
    t0 = time.perf_counter()
    be = ECBackend(config["profile"], "0.0", list(range(g["k"] + g["m"])),
                   chunk_size=g["stripe_unit_bytes"])
    be.write_objects({"warm": np.zeros(g["object_bytes"], np.uint8)})
    launches = {k: be.perf.get(k) for k in rados.COUNTERS}
    log(f"rados_recovering_clay: write programs built in "
        f"{time.perf_counter() - t0:.2f} s; stripe unit "
        f"{be.sinfo.chunk_size}, counters {launches}")
    if be.sinfo.chunk_size != g["stripe_unit_bytes"]:
        raise SystemExit(
            f"rados_recovering_clay: the program resolves the stripe unit "
            f"to {be.sinfo.chunk_size}, the file states "
            f"{g['stripe_unit_bytes']}")
    if launches["fused_write_launches"] != 1 or launches["encode_launches"]:
        raise SystemExit(
            f"rados_recovering_clay: this program writes a Clay object in "
            f"{launches['encode_launches']} encode and "
            f"{launches['fused_write_launches']} fused launches, where the "
            f"cell needs the vector code's write as one fused launch and "
            f"every program of the pool built before the window: it "
            f"cannot state the deployment")


# -- the window -----------------------------------------------------------

def _range_bytes(state: dict) -> int | None:
    if not state["range_counter"]:
        return None
    return sum(int(d.ec_perf.get(RANGE_COUNTER))
               for d in rados_recovering._live(state))


def window(state: dict, seconds: float, tick, log) -> dict:
    """`rados_recovering.window`, with the sources' range bytes sampled
    on the same ticks, and recovery's shapes this code's."""
    samples = [(time.perf_counter(), _range_bytes(state))]

    def tick_and_sample():
        tick()
        now = time.perf_counter()
        if now - samples[-1][0] >= rados_recovering.SAMPLE_EVERY_S:
            samples.append((now, _range_bytes(state)))
    run = rados_recovering.window(state, seconds, tick_and_sample, log)
    samples.append((time.perf_counter(), _range_bytes(state)))
    at_close = [b for at, b in samples if at <= run["t1"]][-1]
    config, rec = state["config"], run["recovery"]
    rec["helper_bytes_an_object"] = helper_bytes_an_object(config)
    rec["work_bytes_an_object"] = recovery_work_bytes(config, 1)
    if at_close is not None:
        rec["range_bytes_served_in_window"] = at_close - samples[0][1]
        run["notes"]["range_bytes_served_in_window"] = \
            rec["range_bytes_served_in_window"]
    log(f"rados_recovering_clay window: {rec}")
    return run


# -- the comparison -------------------------------------------------------

def compare(config: dict, workload: dict, ob: dict) -> list[dict]:
    """Each number beside its limit. Exact comparisons: the limit is 0."""
    g = config["geometry"]
    k, m, d = g["k"], g["m"], g["d"]
    n = k + m
    payloads = ob["payloads"]
    lost_slot = ob["map"]["lost_slot"]
    stripes = {}                 # payload -> striped data rows
    words = {}                   # (payload, row ids) -> the reference's verdict
    row_crcs = {}                # row id -> its crc
    rows_wrong = crcs_wrong = missing = back_wrong = backs = 0
    rebuilt_wrong = rebuilt_crcs_wrong = rebuilt_rows = 0
    origins = {"window": 0}
    for o in ob["objects"]:
        origins[o["origin"]] = origins.get(o["origin"], 0) + 1
        rows = list(o["rows"][:n]) + [None] * (n - len(o["rows"]))
        crcs = list(o["crcs"][:n]) + [None] * (n - len(o["crcs"]))
        key = (o["payload"],) + tuple(id(r) for r in rows)
        if key not in words:
            words[key] = clay_codeword.check(payloads[o["payload"]], rows,
                                             k, m, d, g["stripe_unit_bytes"])
        verdict = words[key]
        planes_ok = verdict["planes_wrong"] == 0
        wrong = set(verdict["data_wrong"]) | (
            set() if planes_ok else set(range(k, n)))
        missing += sum(r is None for r in rows)
        rows_wrong += len(wrong | {s for s in range(n) if rows[s] is None})
        for s in range(n):
            if rows[s] is None or crcs[s] is None:
                crcs_wrong += 1
                continue
            if id(rows[s]) not in row_crcs:
                row_crcs[id(rows[s])] = int(clay_codeword.crcs(
                    rows[s][None, :])[0])
            crcs_wrong += int(crcs[s]) != row_crcs[id(rows[s])] \
                or s in wrong
        if "readback" in o:
            backs += 1
            back_wrong += o["readback"] != payloads[o["payload"]]
        slot = lost_slot.get(o["pg"])
        if o["origin"] == "backlog" and slot is not None:
            # (b) the row on the new member: the payload's data row where
            # the slot holds data, the codeword's parity check where it
            # holds parity
            rebuilt_rows += 1
            if o["payload"] not in stripes:
                stripes[o["payload"]] = clay_codeword.data_rows(
                    payloads[o["payload"]], k, g["stripe_unit_bytes"])
            row = rows[slot]
            good = row is not None and (
                np.array_equal(row, stripes[o["payload"]][slot])
                if slot < k else planes_ok)
            rebuilt_wrong += not good
            rebuilt_crcs_wrong += not good or crcs[slot] is None \
                or int(crcs[slot]) != row_crcs[id(row)]
    since = ob["since_failure"]
    state_checks = rados_recovering.compare(
        config, workload, dict(ob, objects=[], payloads=[]))
    per_object = d * (g["object_bytes"] // k) // (d - k + 1)
    checks = [
        # (a) every acknowledged write, and everything else the pool was
        # given, on the new acting set: a Clay codeword of its payload
        check("stored_rows_wrong", int(rows_wrong), "<=", 0),
        check("stored_crcs_wrong", int(crcs_wrong), "<=", 0),
        check("shards_missing", int(missing), "<=", 0),
        check("window_writes_missing",
              ob["acked_in_window"] - origins["window"], "<=", 0),
        check("window_objects_compared", origins["window"], ">=", 1),
        check("readback_wrong", int(back_wrong), "<=", 0),
        check("objects_read_back", backs, ">=", 1),
        # (b) every backlog object's rebuilt row
        check("rebuilt_rows_wrong", int(rebuilt_wrong), "<=", 0),
        check("rebuilt_crcs_wrong", int(rebuilt_crcs_wrong), "<=", 0),
        check("rebuilt_rows_compared", int(rebuilt_rows), ">=",
              sum(workload["backlog_objects_by_pg"].get(pg, 0)
                  for pg, moves in config["failure"]["repointed_by_pg"]
                  .items() if any(r["lost"] for r in moves))),
        # (e) on the device, in grants of this code's size
        check("rebuilt_over_the_launches_room",
              max(0, since["recovered_objects"]
                  - grant_objects(config) * since["recover_launches"]),
              "<=", 0),
        # (h) every rebuilt object through a range plan: d ranges of
        # row / q bytes on the wire for each, over the whole recovery
        check("wire_bytes_off_the_range_plan",
              abs(since["recover_wire_bytes"]
                  - per_object * since["recovered_objects"]), "<=", 0)]
    return checks + [c for c in state_checks if c["name"] not in REPLACED]


def verify(state: dict, run: dict, log) -> list[dict]:
    """`rados_recovering.verify` with this file's comparison."""
    finish(state, run, log)
    t1 = time.perf_counter()
    ob = observe(state, run)
    t2 = time.perf_counter()
    rados._stop_cluster(state)
    t3 = time.perf_counter()
    checks = compare(state["config"], state["workload"], ob)
    t4 = time.perf_counter()
    run["notes"]["compare_s"] = [round(t2 - t1, 3), round(t4 - t3, 3)]
    log(f"rados_recovering_clay verify: {len(ob['objects'])} objects' rows "
        f"read in {t2 - t1:.2f} s, the reference and the comparison "
        f"{t4 - t3:.2f} s")
    return checks
