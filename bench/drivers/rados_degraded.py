"""Driver `rados_degraded`: `rados bench seq` on a pool with one OSD down
and not yet out, so that reads of the PGs that lost a data slot are
rebuilt from k survivors.

How a driver reuses another: this file imports `bench.drivers.rados` and
takes from it, unchanged, the data and names (`setup`), the boot
(`_boot`, `suspected`, `_stop_cluster`), the writing of the working set,
the client ops and closed loops (`_one_op`, `_loop`), the window and the
reference rows of an object. It adds what the deployment adds: the
failure step in set-up, a comparison that knows one shard of every object
is gone, and `work_bytes` with the decode in it. Nothing of `rados` is
patched; `bench/run.py` finds this file by the configuration's `driver`.

Set-up, in order: boot and `wait_for_clean`; the working set written
whole through the client; `wait_for_clean` again; the victim chosen from
the map (`choose_victim`), stopped (`kill_osd`) and marked down by the
admin `down` (`Client.osd_down`: no heartbeat grace to wait out) with
`mon_osd_down_out_interval` committed at the configuration's value, so
it stays in; a wait until every live daemon's map shows it down; one
read of every PG from one thread, so that every decode pattern of the
run compiles here; then the loops, as `rados` starts them. A program
without the admin `down` cannot state the deployment: `setup` says so
and exits at once, before anything boots.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from bench.checks import check
from bench.drivers import rados
from bench.reference import crc32c, gf256
from bench.reference.rs_decode import rs_decode

POOL = rados.POOL
COUNTERS = ("degraded_reads", "decode_rows_rebuilt", "decode_bytes_rebuilt",
            "host_decode_launches", "recover_launches", "recovered_objects")
MAP_CONSTANTS = ("pgs_data_slot_lost", "pgs_parity_slot_lost",
                 "pgs_untouched", "rebuilding_share")


# -- shapes -------------------------------------------------------------

def work_bytes(config: dict, workload: dict, n_ops: int) -> float:
    """Bytes the algorithm must move through device memory for n_ops
    reads: every read passes its k rows once for their crc; a read of a
    PG that lost a data slot also passes k rows into the decode and one
    row out. Which reads those are is the file's constant
    `rebuilding_share` (the map is the same for every seed), never which
    program ran."""
    g = config["geometry"]
    row = g["object_bytes"] // g["k"]
    per_op = g["k"] * row \
        + config["failure"]["rebuilding_share"] * (g["k"] + 1) * row
    return float(n_ops) * per_op


def unstripe(config: dict, data: np.ndarray) -> bytes:
    """(k, row) data rows back to the object's bytes: the inverse of
    `rados.data_rows`."""
    g = config["geometry"]
    return data.reshape(g["k"], -1, g["stripe_unit_bytes"]).transpose(
        1, 0, 2).tobytes()


# -- the failure ----------------------------------------------------------

def choose_victim(acting_by_pg: dict, k: int, pgs_of_objects: list,
                  osds: list) -> int:
    """The OSD that is no PG's primary and holds a data slot in the PGs
    of the most working-set objects; ties to the lowest id."""
    primaries = {acting[0] for acting in acting_by_pg.values()}
    per_pg = collections.Counter(pgs_of_objects)
    score = {osd: sum(n for pg, n in per_pg.items()
                      if osd in acting_by_pg[pg][:k])
             for osd in osds if osd not in primaries}
    if not score:
        raise RuntimeError("every OSD is some PG's primary: no victim")
    return min(score, key=lambda osd: (-score[osd], osd))


def map_constants(acting_by_pg: dict, k: int, pgs_of_objects: list,
                  victim: int) -> dict:
    """What the configuration's file states of the map, from the map."""
    lost = {pg: acting.index(victim) if victim in acting else None
            for pg, acting in acting_by_pg.items()}
    data = sorted(pg for pg, s in lost.items() if s is not None and s < k)
    parity = sorted(pg for pg, s in lost.items() if s is not None and s >= k)
    return {"victim": victim, "slot_lost": lost,
            "pgs_data_slot_lost": len(data),
            "pgs_parity_slot_lost": len(parity),
            "pgs_untouched": len(lost) - len(data) - len(parity),
            "rebuilding_share": sum(pg in data for pg in pgs_of_objects)
            / len(pgs_of_objects)}


def _live(state: dict) -> list:
    return [d for d in state["cluster"].osds.values()
            if not d._stop.is_set()]


def _fail_one_osd(state: dict, log) -> None:
    """Stop the victim and mark it down; it stays in."""
    t0 = time.perf_counter()
    config, cluster, client = (state["config"], state["cluster"],
                               state["client"])
    pgs = range(config["cluster"]["pg_num"])
    acting = {pg: list(client.osdmap.pg_to_up_acting_osds(POOL, pg)[2])
              for pg in pgs}
    pgs_of_objects = [rados._pg(state, name)
                      for name, _ in state["working_set"]]
    victim = choose_victim(acting, config["geometry"]["k"], pgs_of_objects,
                           cluster.osd_ids())
    failure = map_constants(acting, config["geometry"]["k"], pgs_of_objects,
                            victim)
    failure["acting"] = acting
    client.config_set("mon_osd_down_out_interval",
                      config["failure"]["mon_osd_down_out_interval_s"])
    cluster.kill_osd(victim)
    client.osd_down(victim)
    cluster._wait(lambda: all(d.osdmap is not None
                              and not d.osdmap.osd_up[victim]
                              for d in _live(state)),
                  30, f"every live daemon's map shows osd.{victim} down")
    state["failure"] = failure
    state["health"]["failure"] = {
        key: failure[key] for key in ("victim",) + MAP_CONSTANTS}
    state["health"]["failure"]["slot_lost_by_pg"] = {
        str(pg): s for pg, s in failure["slot_lost"].items()}
    log(f"rados_degraded: osd.{victim} stopped and marked down in "
        f"{time.perf_counter() - t0:.2f} s; slot lost by pg "
        f"{failure['slot_lost']}; "
        f"{ {key: failure[key] for key in MAP_CONSTANTS} }")


def others_suspected(state: dict) -> list:
    """[daemon, peer] for every suspicion of an OSD but the victim."""
    victim = state["failure"]["victim"]
    return [pair for pair in rados.suspected(state) if pair[1] != victim]


# -- set-up -------------------------------------------------------------

def setup(config: dict, workload: dict, seed: int, log) -> dict:
    from ceph_tpu.osd.standalone import Client
    if not hasattr(Client, "osd_down"):
        raise SystemExit(
            "rados_degraded: this program has no admin `osd down` "
            "(Client.osd_down) and its monitor marks a failed OSD out with "
            "the down mark: it cannot hold a pool degraded")
    if workload["op"] != "read":
        raise SystemExit("rados_degraded: the cell reads; a degraded write "
                         "is another mechanism")
    return rados.setup(config, workload, seed, log)


def warm(state: dict, log) -> None:
    """Boot, working set, failure, warm-up; again from the boot where a
    daemon comes to suspect a live peer on the way (as `rados.warm`)."""
    health = state["health"]
    found = []
    for boot in range(1, rados.SET_UP_TRIES + 1):
        health["boots"] = boot
        try:
            rados._boot(state, log)
            found = rados.suspected(state)
            if not found:
                _warm_once(state, log)
                found = others_suspected(state)
        except BaseException:
            rados._stop_cluster(state)
            raise
        if not found:
            return
        log(f"rados_degraded set-up {boot}: daemons suspect live peers "
            f"{found}; booting again")
        health.setdefault("suspected_in_set_up", []).append(found)
        rados._stop_cluster(state)
    raise RuntimeError(f"no pool with one OSD down and the rest whole "
                       f"after {rados.SET_UP_TRIES} boots: {found}")


def _warm_once(state: dict, log) -> None:
    wl = state["workload"]
    state["keep_reads"] = False
    first = rados._write_working_set(state, log)
    state["cluster"].wait_for_clean(timeout=120)
    _fail_one_osd(state, log)
    t0 = time.perf_counter()
    for name, pay in first:            # every PG's decode pattern, once
        rados._must(rados._one_op(state, "read", name, pay, -1))
    log(f"rados_degraded warm: one read per pg in "
        f"{time.perf_counter() - t0:.2f} s, counters {read_counters(state)}")
    state["threads"] = [
        threading.Thread(target=rados._loop, args=(state, i),
                         name=f"bench-loop-{i}", daemon=True)
        for i in range(wl["loops"])]
    for t in state["threads"]:
        t.start()
    # the cell's own traffic until the loops have read every PG and no
    # program of any layer has been compiled or loaded for a few seconds
    # (`program_cache_misses`, which `rados` watches, is blind to the
    # read path's programs; the program's `xla.compile` records are not)
    t1 = time.perf_counter()
    pgs = set(range(state["config"]["cluster"]["pg_num"]))
    compiles, since = _compiles(), t1
    while True:
        time.sleep(0.25)
        now = time.perf_counter()
        seen = _compiles()
        if seen != compiles:
            compiles, since = seen, now
        with state["lock"]:
            served = {rados._pg(state, op["name"])
                      for op in state["ops"] if op["ok"]}
        if (now - t1 >= wl["warm_min_s"] and served >= pgs
                and now - since >= wl["warm_quiet_s"]) \
                or now - t1 >= wl["warm_max_s"]:
            break
    log(f"rados_degraded warm: loops ran {now - t1:.2f} s on pgs "
        f"{sorted(served)}, {compiles} programs compiled or loaded so far")


def _compiles() -> int:
    from ceph_tpu.utils.tracing import span_log
    return sum(1 for r in span_log() if r["name"] == "xla.compile")


def read_counters(state: dict) -> dict:
    return {key: sum(int(d.ec_perf.get(key)) for d in _live(state))
            for key in COUNTERS}


# -- the window -----------------------------------------------------------

def window(state: dict, seconds: float, tick, log) -> dict:
    """`rados.window`, with this deployment's counters read round it and
    the pool's state read as it closes."""
    before = read_counters(state)
    run = rados.window(state, seconds, tick, log)
    after = read_counters(state)
    run["counters"].update({key: after[key] - before[key]
                            for key in COUNTERS})
    run["pool_at_close"] = pool_state(state)
    log(f"rados_degraded window: counters "
        f"{ {key: run['counters'][key] for key in COUNTERS} }; pool at "
        f"close {run['pool_at_close']}")
    return run


def pool_state(state: dict) -> dict:
    """Whether the pool is still as the failure left it: read from the
    monitors' committed map and the primaries' backends."""
    failure, cluster = state["failure"], state["cluster"]
    victim = failure["victim"]
    osdmap = max((m.osdmap for m in cluster.mons if m.osdmap is not None),
                 key=lambda m: m.epoch)
    down = sorted(int(o) for o in cluster.osd_ids() if not osdmap.osd_up[o])
    repointed = recovering = 0
    for d in _live(state):
        recovering += len(d._recovering)
        for pg, be in d.backends.items():
            repointed += sum(a != b for a, b in
                             zip(be.acting, failure["acting"][pg]))
    return {"victim": victim, "down": down,
            "victim_in": bool(osdmap.osd_weight[victim] != 0),
            "slots_repointed": repointed, "pgs_recovering": recovering,
            "others_suspected": others_suspected(state)}


# -- the comparison -------------------------------------------------------

def _survivor_rows(state: dict, pg: int, name: str) -> tuple[list, list]:
    """The k+m rows and hinfo crcs of one object as the OSDs that acted
    for its PG before the failure hold them; None at the victim's slot
    and where a store has no such shard."""
    from ceph_tpu.osd.pgbackend import shard_cid
    failure = state["failure"]
    stores = [None if osd == failure["victim"] else
              (state["cluster"].osds[osd].store,
               shard_cid(f"{POOL}.{pg}", shard))
              for shard, osd in enumerate(failure["acting"][pg])]
    rows, crcs = [], []
    for entry in stores:
        got = ([None], [None]) if entry is None \
            else rados._stored([entry], name)
        rows.append(got[0][0])
        crcs.append(got[1][0])
    return rows, crcs


def observe(state: dict, run: dict) -> dict:
    """What the timed path produced: every object's rows on the
    survivors, the bytes every timed read returned, a seed-drawn sample
    read back once more, the counters, and the pool's state at the
    close."""
    wl, failure = state["workload"], state["failure"]
    written = list(state["working_set"])
    rng = np.random.default_rng([state["seed"], 2])
    n_pick = min(wl["readback_objects"], len(written))
    picks = (set(rng.choice(len(written), n_pick, replace=False).tolist())
             | {len(written) - 1})
    objects = []
    for i, (name, pay) in enumerate(written):
        pg = rados._pg(state, name)
        rows, crcs = _survivor_rows(state, pg, name)
        obj = {"name": name, "payload": pay, "pg": pg,
               "slot_lost": failure["slot_lost"][pg],
               "rows": rows, "crcs": crcs}
        if i in picks:
            back = rados._one_op(state, "read", name, pay, -3, keep=True)
            obj["readback"] = back.get("returned") if back["ok"] else None
            # any k of the survivors, drawn from the seed: not the
            # program's pick
            there = [s for s, row in enumerate(rows) if row is not None]
            obj["decode_from"] = sorted(rng.choice(
                there, state["config"]["geometry"]["k"],
                replace=False).tolist())
        objects.append(obj)
    reads = [{"name": op["name"], "payload": op["payload"],
              "returned": op["returned"], "issued": op["start"] >= run["t0"]}
             for op in run["ops"] if "returned" in op]
    ob = {"objects": objects, "payloads": state["payloads"], "reads": reads,
          "counters": run["counters"], "failed": run["failed"],
          "pool": run["pool_at_close"],
          "map": {key: failure[key] for key in MAP_CONSTANTS}}
    if run.get("trace"):
        ob["traced"] = {
            "busy_s": run["trace"]["busy_s"],
            "least_s": (run["traced_work_bytes"]
                        / run["peaks"]["hbm_bytes_per_s"])}
    return ob


def compare(config: dict, workload: dict, ob: dict) -> list[dict]:
    """Each number beside its limit. Exact comparisons: the limit is 0."""
    g, stated = config["geometry"], config["failure"]
    k, n = g["k"], g["k"] + g["m"]
    matrix = gf256.reed_sol_van(g["k"], g["m"])
    payloads = ob["payloads"]
    want = {}
    for pay in sorted({o["payload"] for o in ob["objects"]}):
        rows = rados.reference_rows(config, payloads[pay], matrix)
        want[pay] = rows, crc32c.crc32c_rows(rados.CRC_SEED, rows)
    returned = collections.defaultdict(list)
    for r in ob["reads"]:
        returned[r["name"]].append(r["returned"])
    rows_wrong = crcs_wrong = missing = back_wrong = backs = 0
    sample_wrong = sample_reads = 0
    for o in ob["objects"]:
        rows, crcs = want[o["payload"]]
        for s in range(n):
            if s == o["slot_lost"]:
                continue
            got, crc = o["rows"][s], o["crcs"][s]
            missing += got is None
            rows_wrong += got is None or not np.array_equal(got, rows[s])
            crcs_wrong += crc is None or int(crc) != int(crcs[s])
        if "readback" in o:
            backs += 1
            back_wrong += o["readback"] != payloads[o["payload"]]
            # the plain decode of what the survivors store, against what
            # the program's reads of this object returned
            there = o["decode_from"]
            if any(o["rows"][s] is None for s in there):
                sample_wrong += 1
                continue
            plain = unstripe(config, rs_decode(
                matrix, np.stack([o["rows"][s] for s in there]), there,
                range(k)))
            theirs = returned[o["name"]] + [o["readback"]]
            sample_reads += len(theirs)
            sample_wrong += sum(t != plain for t in theirs)
    reads_wrong = sum(1 for r in ob["reads"]
                      if r["returned"] != payloads[r["payload"]])
    c, pool = ob["counters"], ob["pool"]
    lost_data = {o["name"] for o in ob["objects"]
                 if o["slot_lost"] is not None and o["slot_lost"] < k}
    rebuilding_issued = sum(1 for r in ob["reads"]
                            if r["issued"] and r["name"] in lost_data)
    checks = [
        check("stored_rows_wrong", int(rows_wrong), "<=", 0),
        check("stored_crcs_wrong", int(crcs_wrong), "<=", 0),
        check("survivor_shards_missing", int(missing), "<=", 0),
        check("readback_wrong", int(back_wrong), "<=", 0),
        check("objects_compared", len(ob["objects"]), ">=", 1),
        check("objects_read_back", backs, ">=", 1),
        check("timed_reads_wrong", reads_wrong, "<=", 0),
        check("timed_reads_compared", len(ob["reads"]), ">=", 1),
        check("rs_decode_sample_wrong", int(sample_wrong), "<=", 0),
        check("rs_decode_sample_reads", int(sample_reads), ">=", 1),
        check("ops_failed", ob["failed"], "<=", 0),
        # the mechanism: reads of PGs that lost a data slot are rebuilt,
        # on the device, and the pool stays as the failure left it
        check("degraded_reads", c["degraded_reads"], ">=",
              max(rebuilding_issued, 1)),
        check("rebuilt_rows_off_share",
              abs(c["decode_rows_rebuilt"]
                  - stated["rebuilding_share"] * len(ob["reads"])),
              "<=", len(ob["objects"])),
        check("host_decode_launches", c["host_decode_launches"], "<=", 0),
        check("recover_launches", c["recover_launches"], "<=", 0),
        check("recovered_objects", c["recovered_objects"], "<=", 0),
        check("osds_down_at_close", len(pool["down"]), "<=", 1),
        check("victim_down_at_close",
              int(pool["down"] == [pool["victim"]]), ">=", 1),
        check("victim_in_at_close", int(pool["victim_in"]), ">=", 1),
        check("slots_repointed", pool["slots_repointed"], "<=", 0),
        check("pgs_recovering", pool["pgs_recovering"], "<=", 0),
        check("others_suspected", len(pool["others_suspected"]), "<=", 0)]
    # the file's constants against the map of the run
    checks += [check(key + "_off_file",
                     abs(ob["map"][key] - stated[key]), "<=", 0)
               for key in MAP_CONSTANTS]
    if "traced" in ob:
        t = ob["traced"]
        checks.append(check("device_busy_s_traced", t["busy_s"], ">=",
                            t["least_s"]))
    return checks


def verify(state: dict, run: dict, log) -> list[dict]:
    t0 = time.perf_counter()
    ob = observe(state, run)
    t1 = time.perf_counter()
    checks = compare(state["config"], state["workload"], ob)
    log(f"rados_degraded verify: {len(ob['objects'])} objects "
        f"({t1 - t0:.2f} s to read them), {len(ob['reads'])} timed reads, "
        f"reference {time.perf_counter() - t1:.2f} s")
    return checks


def close(state: dict, log) -> None:
    rados._stop_cluster(state)
