"""Driver `rados_recovering_lrc`: `rados_recovering`'s run on an LRC
pool, which rebuilds a lost row from the l other members of its local
group instead of k rows, and whose chunk mapping interleaves data and
parity slots.

How it reuses the others: this file imports `bench.drivers.rados` and
`bench.drivers.rados_recovering` and takes from them, unchanged, the
data and names, the boot, the backlog, the writer loops, the set-up from
the backlog on (`rados_recovering._warm_once`: the failure carried
through to the out mark, the window's place on the recovery's
timeline), recovery's counters sampled while the window runs, the pool's
state after it and what is read back (`rados_recovering.observe`), and
the checks of the recovery's state ((c) to (g) of the configuration's
guarantees). Nothing of either is patched. What it adds is what the code
changes:

* the victim: the data slots are the code's (the first k of its chunk
  mapping), not the first k slots, so the boot loop is this file's, with
  its own plan of the failure;
* shapes: a write commits all n rows; a rebuilt object pulls l whole
  rows, so a grant of `osd_recovery_max_active` x
  `osd_recovery_max_chunk` holds more objects, and the readers count an
  object by those bytes;
* set-up: before the boot, one object of the cell's size is written
  through a throw-away backend over in-memory stores, so that the write
  program is built before a client op can wait on one; a program that
  takes more than the one fused launch for it is refused there;
* the comparison: every object's n rows held to
  `bench/reference/lrc_codeword.py` and its hinfo crcs to those rows',
  every rebuilt row to the reference's decode from the other members of
  its group, and guarantee (h): every rebuilt object went through a
  local plan, l x row helper bytes on the wire for each, exactly.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from bench.checks import check
from bench.drivers import rados, rados_recovering
from bench.reference import lrc_codeword, recovered_pool

PLANNER = ("planner_local_plans", "planner_full_plans")
# the checks of rados_recovering that hold the rows to RS, or a grant to
# RS's size: this file holds them instead
REPLACED = {"stored_rows_wrong", "stored_crcs_wrong", "shards_missing",
            "window_writes_missing", "window_objects_compared",
            "readback_wrong", "objects_read_back", "rebuilt_rows_wrong",
            "rebuilt_crcs_wrong", "rebuilt_rows_compared",
            "rebuilt_over_the_launches_room"}

observe = rados_recovering.observe
finish = rados_recovering.finish
close = rados_recovering.close


# -- shapes -------------------------------------------------------------

def _row(config: dict) -> int:
    g = config["geometry"]
    return g["object_bytes"] // g["k"]


def helper_bytes_an_object(config: dict) -> int:
    """Bytes a rebuilt object pulls: the l other members of its group,
    whole rows."""
    return config["geometry"]["l"] * _row(config)


def work_bytes(config: dict, workload: dict, n_ops: float) -> float:
    """A client write: all n rows and their crc words, one a csum block
    (`rados.work_bytes`'s rule over the code's n rows)."""
    g, row = config["geometry"], _row(config)
    return float(n_ops) * g["chunk_count"] * (
        row + row // g["csum_block_bytes"] * 4)


def recovery_work_bytes(config: dict, objects_rebuilt: float) -> float:
    """Bytes the algorithm must move through device memory to rebuild
    that many objects: l helper rows into the decode, the rebuilt row
    and its crc word out. From shapes alone."""
    return float(objects_rebuilt) * (helper_bytes_an_object(config)
                                     + _row(config) + 4)


def grant_objects(config: dict) -> int:
    """Objects one grant may stage: the power of two under
    osd_recovery_max_active x osd_recovery_max_chunk over an object's
    helper bytes."""
    r = config["recovery"]
    fit = (r["osd_recovery_max_active"] * r["osd_recovery_max_chunk"]
           ) // helper_bytes_an_object(config)
    return 1 << max(0, fit.bit_length() - 1)


# -- the failure ----------------------------------------------------------

def data_slots(profile: str) -> list[int]:
    """The slots that carry the object's data rows: the first k of the
    code's chunk mapping."""
    from ceph_tpu.ec.registry import factory
    coder = factory(profile)
    return [int(s) for s in
            coder.get_chunk_mapping()[:coder.get_data_chunk_count()]]


def choose_victim(acting_by_pg: dict, slots: list[int],
                  pgs_of_objects: list, osds: list) -> int:
    """The OSD that is no PG's primary and holds one of the data `slots`
    in the PGs of the most objects; ties to the lowest id."""
    primaries = {acting[0] for acting in acting_by_pg.values()}
    per_pg = collections.Counter(pgs_of_objects)
    score = {osd: sum(n for pg, n in per_pg.items()
                      if osd in [acting_by_pg[pg][s] for s in slots])
             for osd in osds if osd not in primaries}
    if not score:
        raise RuntimeError("every OSD is some PG's primary: no victim")
    return min(score, key=lambda osd: (-score[osd], osd))


def plan_failure(state: dict, log) -> dict:
    """`rados_recovering.plan_failure` under this code's victim rule:
    the victim, and the pool's map as it will stand once it is out."""
    from ceph_tpu.osd.osdmap import OSDMap
    config, client = state["config"], state["client"]
    pg_num, n_osds = config["cluster"]["pg_num"], config["cluster"]["n_osds"]
    old = rados_recovering._acting_by_pg(client.osdmap, pg_num)
    pgs_of_objects = [rados._pg(state, name)
                      for name, _ in state["working_set"]]
    victim = choose_victim(old, state["data_slots"], pgs_of_objects,
                           state["cluster"].osd_ids())
    after = OSDMap.decode(client.osdmap.encode())
    after.mark_down(victim)
    after.mark_out(victim)
    new = rados_recovering._acting_by_pg(after, pg_num)
    holed = {pg: slots for pg, acting in new.items()
             if (slots := recovered_pool.holes(acting, n_osds))}
    if holed:
        raise SystemExit(
            f"rados_recovering_lrc: with osd.{victim} out this program's "
            f"map leaves PGs with a hole (pg: slots {holed}): the pool can "
            f"never be clean")
    repointed = {pg: recovered_pool.repointed(old[pg], new[pg], victim)
                 for pg in old}
    plan = {"victim": victim, "old": old, "new": new,
            "repointed": repointed,
            "lost_slot": {pg: next((r["slot"] for r in moves if r["lost"]),
                                   None)
                          for pg, moves in repointed.items()},
            "backlog_by_pg": dict(sorted(collections.Counter(
                pgs_of_objects).items()))}
    log(f"rados_recovering_lrc plan: victim osd.{victim}, lost slot by pg "
        f"{plan['lost_slot']}; backlog by pg {plan['backlog_by_pg']}")
    return plan


# -- set-up -------------------------------------------------------------

def setup(config: dict, workload: dict, seed: int, log) -> dict:
    state = rados_recovering.setup(config, workload, seed, log)
    state["data_slots"] = data_slots(config["profile"])
    if state["data_slots"] != config["geometry"]["data_slots"]:
        raise SystemExit(
            f"rados_recovering_lrc: this program puts the data rows in "
            f"slots {state['data_slots']}, the file states "
            f"{config['geometry']['data_slots']}")
    _build_write_programs(config, log)
    return state


def _build_write_programs(config: dict, log) -> None:
    """One object of the cell's size written through a throw-away
    backend over in-memory stores: the write program is compiled here
    and not under a client op. Refuses a program that writes an LRC
    object in more than the one fused launch: such a program runs a
    launch a layer and builds them inside the window."""
    from ceph_tpu.osd.ecbackend import ECBackend
    g = config["geometry"]
    t0 = time.perf_counter()
    be = ECBackend(config["profile"], "0.0", list(range(g["chunk_count"])),
                   chunk_size=g["stripe_unit_bytes"])
    be.write_objects({"warm": np.zeros(g["object_bytes"], np.uint8)})
    launches = {k: be.perf.get(k) for k in rados.COUNTERS}
    log(f"rados_recovering_lrc: write programs built in "
        f"{time.perf_counter() - t0:.2f} s; stripe unit "
        f"{be.sinfo.chunk_size}, counters {launches}")
    if be.sinfo.chunk_size != g["stripe_unit_bytes"]:
        raise SystemExit(
            f"rados_recovering_lrc: the program resolves the stripe unit "
            f"to {be.sinfo.chunk_size}, the file states "
            f"{g['stripe_unit_bytes']}")
    if launches["fused_write_launches"] != 1 or launches["encode_launches"]:
        raise SystemExit(
            f"rados_recovering_lrc: this program writes an LRC object in "
            f"{launches['encode_launches']} encode and "
            f"{launches['fused_write_launches']} fused launches, where the "
            f"cell needs the layered code's write as one fused launch and "
            f"every program of the pool built before the window: it "
            f"cannot state the deployment")


def _planner(state: dict) -> dict:
    """osd id -> the planner's counters of that live daemon."""
    return {d.osd_id: {key: int(d.ec_perf.get(key)) for key in PLANNER}
            for d in rados_recovering._live(state)}


def planned_since_boot(state: dict) -> dict:
    """The planner's counters' rise since the boot, summed over the
    daemons live now (the victim, no PG's primary, plans nothing)."""
    at_boot = state["planner_at_boot"]
    return {key: sum(now[key] - at_boot.get(osd, {}).get(key, 0)
                     for osd, now in _planner(state).items())
            for key in PLANNER}


def warm(state: dict, log) -> None:
    """`rados_recovering.warm` with this file's plan of the failure:
    boot, backlog, warm-up, failure, the leads; again from the boot
    where a daemon comes to suspect a live peer on the way."""
    health = state["health"]
    found = []
    for boot in range(1, rados.SET_UP_TRIES + 1):
        health["boots"] = boot
        try:
            rados._boot(state, log)
            found = rados.suspected(state)
            if not found:
                state["failure"] = plan_failure(state, log)
                state["planner_at_boot"] = _planner(state)
                found = rados_recovering._warm_once(state, log)
        except BaseException:
            rados._stop_cluster(state)
            raise
        if not found:
            return
        log(f"rados_recovering_lrc set-up {boot}: daemons suspect live "
            f"peers {found}; booting again")
        health.setdefault("suspected_in_set_up", []).append(found)
        rados._stop_cluster(state)
    raise RuntimeError(f"no pool with one OSD out and the rest whole after "
                       f"{rados.SET_UP_TRIES} boots: {found}")


# -- the window -----------------------------------------------------------

def window(state: dict, seconds: float, tick, log) -> dict:
    """`rados_recovering.window`, with recovery's shapes this code's."""
    run = rados_recovering.window(state, seconds, tick, log)
    config, rec = state["config"], run["recovery"]
    rec["helper_bytes_an_object"] = helper_bytes_an_object(config)
    rec["work_bytes_an_object"] = recovery_work_bytes(config, 1)
    return run


# -- the comparison -------------------------------------------------------

def compare(config: dict, workload: dict, ob: dict) -> list[dict]:
    """Each number beside its limit. Exact comparisons: the limit is 0."""
    g = config["geometry"]
    k, m, l, n = g["k"], g["m"], g["l"], g["chunk_count"]
    payloads = ob["payloads"]
    lost_slot = ob["map"]["lost_slot"]
    want = {}                    # payload -> the codeword's rows and crcs
    for pay in sorted({o["payload"] for o in ob["objects"]}):
        rows = lrc_codeword.codeword(payloads[pay], k, m, l,
                                     g["stripe_unit_bytes"])
        want[pay] = rows, lrc_codeword.crcs(rows)
    same = {}                    # a row kept once is compared once
    plain = {}                   # (slot, group rows) -> (rebuilt row, crc)
    rows_wrong = crcs_wrong = missing = back_wrong = backs = 0
    rebuilt_wrong = rebuilt_crcs_wrong = rebuilt_rows = 0
    origins = collections.Counter()
    for o in ob["objects"]:
        rows, crcs = want[o["payload"]]
        got = list(o["rows"][:n]) + [None] * (n - len(o["rows"]))
        got_crcs = list(o["crcs"][:n]) + [None] * (n - len(o["crcs"]))
        origins[o["origin"]] += 1
        for s in range(n):
            missing += got[s] is None
            key = (id(got[s]), o["payload"], s)
            if got[s] is not None and key not in same:
                same[key] = np.array_equal(got[s], rows[s])
            rows_wrong += got[s] is None or not same[key]
            crcs_wrong += got_crcs[s] is None \
                or int(got_crcs[s]) != int(crcs[s])
        if "readback" in o:
            backs += 1
            back_wrong += o["readback"] != payloads[o["payload"]]
        slot = lost_slot.get(o["pg"])
        if o["origin"] == "backlog" and slot is not None:
            # (b) the row on the new member: the codeword's, and the
            # reference's decode from the other members of its group
            rebuilt_rows += 1
            ins, parity = lrc_codeword.group_of(slot, k, m, l)
            group = [p for p in list(ins) + [parity] if p != slot]
            if any(got[p] is None for p in group + [slot]):
                rebuilt_wrong += 1
                continue
            cls = (slot,) + tuple(id(got[p]) for p in group)
            if cls not in plain:
                row = lrc_codeword.rebuilt(got, slot, k, m, l)
                plain[cls] = row, int(lrc_codeword.crcs(row[None, :])[0])
            row, crc = plain[cls]
            rebuilt_wrong += not (np.array_equal(row, got[slot])
                                  and same[(id(got[slot]), o["payload"],
                                            slot)])
            rebuilt_crcs_wrong += got_crcs[slot] is None \
                or int(got_crcs[slot]) != crc
    since, c = ob["since_failure"], ob["counters"]
    planned = ob["planned"]
    state_checks = rados_recovering.compare(
        config, workload, dict(ob, objects=[], payloads=[]))
    lost_pgs = sum(1 for s in lost_slot.values() if s is not None)
    checks = [
        # (a) every acknowledged write, and everything else the pool was
        # given, on the new acting set: the LRC codeword of its payload
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
        # (e) the window's writes one fused launch a batch; rebuilt on the
        # device in grants of this code's size
        check("encode_launches", c["encode_launches"], "<=", 0),
        check("rebuilt_over_the_launches_room",
              max(0, since["recovered_objects"]
                  - grant_objects(config) * since["recover_launches"]),
              "<=", 0),
        # (h) every rebuilt object through a local plan: l whole rows on
        # the wire for each, over the whole recovery
        check("wire_bytes_off_the_local_plan",
              abs(since["recover_wire_bytes"]
                  - helper_bytes_an_object(config)
                  * since["recovered_objects"]), "<=", 0),
        check("planner_full_plans", planned["planner_full_plans"], "<=", 0),
        check("planner_local_plans", planned["planner_local_plans"], ">=",
              lost_pgs)]
    return checks + [c for c in state_checks if c["name"] not in REPLACED]


def verify(state: dict, run: dict, log) -> list[dict]:
    """`rados_recovering.verify` with this file's comparison, and the
    planner's counters from the boot to clean."""
    finish(state, run, log)
    t1 = time.perf_counter()
    planned = planned_since_boot(state)
    ob = dict(observe(state, run), planned=planned)
    t2 = time.perf_counter()
    rados._stop_cluster(state)
    t3 = time.perf_counter()
    checks = compare(state["config"], state["workload"], ob)
    t4 = time.perf_counter()
    run["notes"]["compare_s"] = [round(t2 - t1, 3), round(t4 - t3, 3)]
    run["notes"]["planned_since_boot"] = planned
    log(f"rados_recovering_lrc verify: {len(ob['objects'])} objects' rows "
        f"read in {t2 - t1:.2f} s, the reference and the comparison "
        f"{t4 - t3:.2f} s; planned since the boot {planned}")
    return checks
