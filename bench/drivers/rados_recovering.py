"""Driver `rados_recovering`: `rados bench write` on a pool that lost an
OSD for good and backfills its shards onto new members, a PG at a time
under `osd_max_backfills`, while the clients write on.

How it reuses the others: this file imports `bench.drivers.rados` and
`bench.drivers.rados_degraded` and takes from them, unchanged, the data
and names (`rados.setup`), the boot (`_boot`, `suspected`,
`_stop_cluster`), the writing of whole objects (`_write_working_set`:
the backlog), the closed writer loops (`_loop`, `_one_op`), the window,
the stores' rows (`_stored`), and the victim's rule (`choose_victim`).
Nothing of either is patched; `bench/run.py` finds this file by the
configuration's `driver`. What it adds is what the deployment adds: the
failure carried through to the out mark under running writers, a window
that opens at a fixed place on the recovery's timeline, recovery's
counters read while the window runs, and a comparison against
`bench/reference/recovered_pool.py`: every object on the PG's *new*
acting set, the rebuilt rows by `rs_decode`.

Set-up, in order (each boot again from the start where a daemon comes
to suspect a live peer, as `rados.warm`): boot and `wait_for_clean`; the
victim and the map as it will stand with the victim out, from the
client's map (a PG with a hole there ends the run at once); the backlog
written healthy through the client by as many threads as the cell has
loops; `wait_for_clean`; the writer loops on the healthy pool, on names
past the backlog's, until no program was compiled or loaded for
`warm_quiet_s`, then stopped; the victim stopped (`kill_osd`) and marked
down by the admin `down` with `mon_osd_down_out_interval` committed at
the file's value: every PG is degraded, nothing rebuilds; the loops
again for `degraded_lead_s`, then stopped; the victim marked out (`osd
out`, the interval's expiry): every primary copies the rows CRUSH moved
between live OSDs, plans its PGs, builds their recover programs in the
background and asks for their reservations; at the later of the first
grant of any recovery round and the moment every PG is re-pointed,
every planned PG's program is ready and none is pending on any daemon,
the loops start for good, and the window opens `recovery_lead_s` later.
So every run measures the same stretch of one recovery, from its first
seconds on. (The writers stand still while the out map is folded: with
them on, that fold and the builds took a minute, in which more than
half the backlog was rebuilt: PERF.md section 6.)
Of the program the driver reads what an operator can: the daemons'
counters (`perf dump`), their resolved settings (`config show`) and the
acting set each primary serves. After the window the loops stop,
recovery runs to clean (a bounded wait; `time_to_clean_s` goes into the
line's `notes`), a seed-drawn sample is read back through the client,
every object's rows are read from the stores of the new acting set, the
cluster stops, and only then the reference is computed and compared.

A program without `osd_max_backfills`, or without the counters that
show what the reservation and the grants did, cannot state the
deployment: `setup` says so and exits at once, before anything boots.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
import zlib

import numpy as np

from bench.checks import check
from bench.drivers import rados, rados_degraded
from bench.reference import crc32c, gf256, recovered_pool

POOL = rados.POOL
_live = rados_degraded._live
others_suspected = rados_degraded.others_suspected
EC_COUNTERS = ("recover_launches", "recover_host_launches",
               "recovered_objects", "recovered_bytes", "recover_wire_bytes",
               "hinfo_failures", "host_decode_launches",
               "host_encode_launches", "fused_write_launches",
               "recover_programs_ready")
OSD_COUNTERS = ("recovery_grants", "recovery_rounds",
                "backfill_reservations_granted",
                "backfill_reservation_waits")
COUNTERS = EC_COUNTERS + OSD_COUNTERS
# high-water marks and levels: read as they stand, never as a rise
EC_GAUGES = ("recover_programs_pending", "recover_grant_bytes_max")
RESERVE_WAIT = "backfill_reserve_wait_time"      # a time_avg: sum, count
SETTINGS = ("osd_max_backfills", "osd_recovery_max_active",
            "osd_recovery_max_chunk", "osd_recovery_sleep",
            "osd_mclock_profile", "osd_recovery_integrity")
SAMPLE_EVERY_S = 0.2         # recovery's counters, read while the window runs
SLICE_S = 5.0                # guarantee (c): recovery live in every slice


# -- shapes -------------------------------------------------------------

def work_bytes(config: dict, workload: dict, n_ops: int) -> float:
    """Bytes the algorithm must move through device memory for n_ops
    client writes: `rados.work_bytes`. What the rebuild moves beside
    them is `recovery_work_bytes`, counted from the objects rebuilt."""
    return rados.work_bytes(config, workload, n_ops)


def recovery_work_bytes(config: dict, objects_rebuilt: float) -> float:
    """Bytes the algorithm must move through device memory to rebuild
    that many objects, each of which lost one row: k helper rows into
    the decode, the rebuilt row out of it, and the crc word of the row
    it checks. From shapes alone."""
    g = config["geometry"]
    row = g["object_bytes"] // g["k"]
    return float(objects_rebuilt) * (g["k"] * row + row + 4)


def grant_objects(config: dict) -> int:
    """Objects one grant may stage under the stated settings: the power
    of two under osd_recovery_max_active x osd_recovery_max_chunk over
    the k helper rows of an object."""
    g, r = config["geometry"], config["recovery"]
    fit = (r["osd_recovery_max_active"] * r["osd_recovery_max_chunk"]
           ) // g["object_bytes"]
    return 1 << max(0, fit.bit_length() - 1)


# -- the failure ----------------------------------------------------------

def _acting_by_pg(osdmap, pg_num: int) -> dict:
    return {pg: [int(o) for o in osdmap.pg_to_up_acting_osds(POOL, pg)[2]]
            for pg in range(pg_num)}


def plan_failure(state: dict, log) -> dict:
    """The victim, and the pool's map as it will stand once the victim
    is out: computed from a copy of the client's map, before anything is
    written. A PG that keeps a hole there cannot be rebuilt: the run
    ends here, in a line."""
    from ceph_tpu.osd.osdmap import OSDMap
    config, client = state["config"], state["client"]
    pg_num, n_osds = config["cluster"]["pg_num"], config["cluster"]["n_osds"]
    old = _acting_by_pg(client.osdmap, pg_num)
    pgs_of_objects = [rados._pg(state, name)
                      for name, _ in state["working_set"]]
    victim = rados_degraded.choose_victim(
        old, config["geometry"]["k"], pgs_of_objects,
        state["cluster"].osd_ids())
    after = OSDMap.decode(client.osdmap.encode())
    after.mark_down(victim)
    after.mark_out(victim)
    new = _acting_by_pg(after, pg_num)
    holed = {pg: slots for pg, acting in new.items()
             if (slots := recovered_pool.holes(acting, n_osds))}
    if holed:
        raise SystemExit(
            f"rados_recovering: with osd.{victim} out this program's map "
            f"leaves PGs with a hole (pg: slots {holed}): CRUSH gives up "
            f"before it finds the spare, and the pool can never be clean")
    plan = {"victim": victim, "old": old, "new": new,
            "repointed": {pg: recovered_pool.repointed(old[pg], new[pg],
                                                       victim)
                          for pg in old},
            "backlog_by_pg": dict(sorted(collections.Counter(
                pgs_of_objects).items()))}
    plan["lost_slot"] = {pg: next((r["slot"] for r in moves if r["lost"]),
                                  None)
                         for pg, moves in plan["repointed"].items()}
    log(f"rados_recovering plan: victim osd.{victim}; re-pointed by pg "
        f"{ {pg: [(r['slot'], r['old'], r['new']) for r in moves] for pg, moves in plan['repointed'].items()} }; "
        f"backlog by pg {plan['backlog_by_pg']}")
    return plan


def _mark_down(state: dict, log) -> None:
    """Stop the victim and mark it down; it stays in."""
    t0 = time.perf_counter()
    config, cluster, client = (state["config"], state["cluster"],
                               state["client"])
    victim = state["failure"]["victim"]
    client.config_set("mon_osd_down_out_interval",
                      config["failure"]["mon_osd_down_out_interval_s"])
    state["by_osd_at_failure"] = counters_by_osd(state)
    state["counters_at_failure"] = _summed(state["by_osd_at_failure"])
    state["compiles_at_failure"] = _compiles()
    cluster.kill_osd(victim)
    client.osd_down(victim)
    cluster._wait(lambda: all(d.osdmap is not None
                              and not d.osdmap.osd_up[victim]
                              for d in _live(state)),
                  30, f"every live daemon's map shows osd.{victim} down")
    log(f"rados_recovering: osd.{victim} stopped and marked down in "
        f"{time.perf_counter() - t0:.2f} s")


def _mark_out(state: dict, log) -> None:
    """`ceph osd out`: the interval's expiry. Backfill starts."""
    cluster, client = state["cluster"], state["client"]
    victim = state["failure"]["victim"]
    state["degraded_at_out"] = _since_failure(state)
    state["t_out"] = time.perf_counter()
    client.osd_out(victim)
    cluster._wait(lambda: all(d.osdmap.osd_weight[victim] == 0
                              for d in _live(state)),
                  30, f"every live daemon's map shows osd.{victim} out")
    log(f"rados_recovering: osd.{victim} out on every map "
        f"{time.perf_counter() - state['t_out']:.2f} s after the mark; "
        f"while it was down and in {state['degraded_at_out']}")


def _primaries(state: dict) -> dict:
    """pg -> the live daemon that is its primary by the plan."""
    osds = state["cluster"].osds
    return {pg: osds[acting[0]]
            for pg, acting in state["failure"]["new"].items()}


def _backfill_state(state: dict) -> dict:
    """Where the recovery stands, as the daemons' counters and the
    primaries' acting sets show it."""
    failure = state["failure"]
    repointed = sum(
        1 for pg, d in _primaries(state).items()
        if (be := d.backends.get(pg)) is not None
        and [int(o) for o in be.acting] == failure["new"][pg])
    since = _since_failure(state)
    return {"pgs_repointed": repointed,
            "programs_ready": since["recover_programs_ready"],
            "programs_pending": gauges(state)["recover_programs_pending"],
            "grants": since["recovery_grants"],
            "rebuilt": since["recovered_objects"]}


# -- set-up -------------------------------------------------------------

def setup(config: dict, workload: dict, seed: int, log) -> dict:
    from ceph_tpu.osd.ecbackend import ec_perf_counters
    ec = ec_perf_counters().dump()
    lacks = [key for key in EC_COUNTERS + EC_GAUGES if key not in ec]
    if "osd_max_backfills" not in _declared_options() or lacks:
        raise SystemExit(
            "rados_recovering: this program has no backfill reservation "
            f"(option osd_max_backfills; counters lacking: {lacks}): every "
            "primary rebuilds all its PGs at once, in grants the "
            "configuration's settings do not size, and nothing shows what "
            "a target held: it cannot state the deployment")
    if workload["op"] != "write":
        raise SystemExit("rados_recovering: the cell writes; a read while "
                         "the pool rebuilds is another cell")
    state = rados.setup(config, workload, seed, log)
    # the backlog: the first N of `rados bench`'s names; the writers go
    # on from there
    state["working_set"] = [rados._entry(state, j)
                            for j in range(workload["backlog_objects"])]
    return state


def _declared_options() -> set:
    from ceph_tpu.utils import config as program_config
    return {o.name for o in program_config.OPTIONS}


def warm(state: dict, log) -> None:
    """Boot, backlog, warm-up, failure, the leads; again from the boot
    where a daemon comes to suspect a live peer on the way (as
    `rados.warm`). Returns at the moment the window is to open."""
    health = state["health"]
    found = []
    for boot in range(1, rados.SET_UP_TRIES + 1):
        health["boots"] = boot
        try:
            rados._boot(state, log)
            found = rados.suspected(state)
            if not found:
                state["failure"] = plan_failure(state, log)
                found = _warm_once(state, log)
        except BaseException:
            rados._stop_cluster(state)
            raise
        if not found:
            return
        log(f"rados_recovering set-up {boot}: daemons suspect live peers "
            f"{found}; booting again")
        health.setdefault("suspected_in_set_up", []).append(found)
        rados._stop_cluster(state)
    raise RuntimeError(f"no pool with one OSD out and the rest whole after "
                       f"{rados.SET_UP_TRIES} boots: {found}")


def _start_loops(state: dict) -> None:
    state["stop"] = threading.Event()
    state["threads"] = [
        threading.Thread(target=rados._loop, args=(state, i),
                         name=f"bench-loop-{i}", daemon=True)
        for i in range(state["workload"]["loops"])]
    for t in state["threads"]:
        t.start()


def _stop_loops(state: dict) -> None:
    state["stop"].set()
    for t in state["threads"]:
        t.join(rados.HUNG_AFTER_S)
    if any(t.is_alive() for t in state["threads"]):
        raise RuntimeError("a warm-up write never came back")


def _warm_once(state: dict, log) -> list:
    """One pass of set-up from the backlog on. Returns the suspicions
    of live peers that stand where the window would open (none: the
    window opens as this returns)."""
    config, wl, cluster = state["config"], state["workload"], state["cluster"]
    failure = state["failure"]
    state["keep_reads"] = False
    rados._write_working_set(state, log)           # the backlog, healthy
    cluster.wait_for_clean(timeout=120)
    found = rados.suspected(state)
    if found:
        return found

    # the cell's own traffic on the healthy pool until no program has
    # been compiled or loaded for a few seconds; names past the
    # backlog's: `rados bench`'s counter went on counting
    state["next_object"] = itertools.count(len(state["working_set"]))
    _start_loops(state)
    t1 = time.perf_counter()
    compiles, since = _compiles(), t1
    while True:
        time.sleep(0.25)
        now = time.perf_counter()
        seen = _compiles()
        if seen != compiles:
            compiles, since = seen, now
        if (now - t1 >= wl["warm_min_s"]
                and now - since >= wl["warm_quiet_s"]) \
                or now - t1 >= wl["warm_max_s"]:
            break
    _stop_loops(state)
    with state["lock"]:
        warm_writes = sum(op["ok"] for op in state["ops"])
    log(f"rados_recovering warm: loops ran {now - t1:.2f} s on the healthy "
        f"pool, {warm_writes} writes, {compiles} programs compiled or "
        f"loaded so far")
    found = rados.suspected(state)
    if found:
        return found

    _mark_down(state, log)
    # degraded, nothing rebuilds: the writers run on for the lead, then
    # stand still while the out map is folded (with 16 writers on, a
    # primary's copy of a moved row, `_move_shard`, takes 10 s where it
    # takes 2, the map reaches the last primary half a minute late and
    # the recover programs build for 25 s each: PERF.md section 6)
    _start_loops(state)
    time.sleep(wl["degraded_lead_s"])
    _stop_loops(state)
    _mark_out(state, log)

    # the window's place on the recovery's timeline
    lost_pgs = sum(1 for slot in failure["lost_slot"].values()
                   if slot is not None)
    pg_num = config["cluster"]["pg_num"]
    deadline = state["t_out"] + config["failure"]["prepare_timeout_s"]
    t_ready = None
    compiles, since = _compiles(), time.perf_counter()
    while True:
        time.sleep(0.05)
        now = time.perf_counter()
        seen = _compiles()
        if seen != compiles:
            compiles, since = seen, now
        if t_ready is None:
            at = _backfill_state(state)
            if (at["pgs_repointed"] == pg_num
                    and at["programs_ready"] >= lost_pgs
                    and at["programs_pending"] == 0 and at["grants"] >= 1):
                t_ready = now
                log(f"rados_recovering warm: every PG re-pointed, every "
                    f"recover program ready and the first grant seen "
                    f"{now - state['t_out']:.2f} s after the out mark: {at}")
                _start_loops(state)         # the writers, warm already
        elif now >= max(t_ready, since) + wl["recovery_lead_s"]:
            break
        if now > deadline:
            raise TimeoutError(
                f"rados_recovering: {now - state['t_out']:.1f} s after the "
                f"out mark the recovery has not started: "
                f"{_backfill_state(state)}; acting by pg "
                f"{ {pg: getattr(d.backends.get(pg), 'acting', None) for pg, d in _primaries(state).items()} }")
    at = _backfill_state(state)
    state["at_open"] = dict(at, s_after_out=round(now - state["t_out"], 3),
                            ready_s_after_out=round(
                                t_ready - state["t_out"], 3))
    state["health"]["failure"] = {
        "victim": failure["victim"],
        "lost_slot_by_pg": {str(pg): s
                            for pg, s in failure["lost_slot"].items()},
        "backlog_by_pg": {str(pg): n
                          for pg, n in failure["backlog_by_pg"].items()}}
    state["health"]["window_opened"] = state["at_open"]
    log(f"rados_recovering warm: the window opens "
        f"{now - state['t_out']:.2f} s after the out mark; "
        f"{compiles - state['compiles_at_failure']} programs compiled or "
        f"loaded since the failure; {at}")
    return others_suspected(state)


def _compiles() -> int:
    from ceph_tpu.utils.tracing import span_log
    return sum(1 for r in span_log() if r["name"] == "xla.compile")


def counters_by_osd(state: dict) -> dict:
    """osd id -> recovery's counters of that live daemon (`perf dump`'s
    `ec` and `osd` sections)."""
    out = {}
    for d in _live(state):
        ec, osd = d.ec_perf.dump(), d.perf.dump()
        out[d.osd_id] = {**{key: int(ec[key]) for key in EC_COUNTERS},
                         **{key: int(osd[key]) for key in OSD_COUNTERS}}
    return out


def _summed(by_osd: dict) -> dict:
    return {key: sum(c[key] for c in by_osd.values()) for key in COUNTERS}


def read_counters(state: dict) -> dict:
    """Recovery's counters summed over the live daemons."""
    return _summed(counters_by_osd(state))


def gauges(state: dict) -> dict:
    """The levels and high-water marks: programs still being built
    (summed), the largest grant and the most PGs a target held (the
    largest over the daemons), and the reservation waits' sum and
    count."""
    daemons = _live(state)
    waits = [d.perf.get(RESERVE_WAIT) for d in daemons]
    return {
        "recover_programs_pending": sum(
            int(d.ec_perf.get("recover_programs_pending")) for d in daemons),
        "recover_grant_bytes_max": max(
            int(d.ec_perf.get("recover_grant_bytes_max")) for d in daemons),
        "backfills_active_max": max(
            int(d.perf.get("backfills_active_max")) for d in daemons),
        "reserve_wait_s": float(sum(w["sum"] for w in waits)),
        "reserve_waits": int(sum(w["count"] for w in waits))}


def _since_failure(state: dict) -> dict:
    now, then = read_counters(state), state["counters_at_failure"]
    return {key: now[key] - then[key] for key in COUNTERS}


def settings(state: dict) -> dict:
    """The stated settings as a live daemon resolves them."""
    d = _live(state)[0]
    return {key: d.config[key] for key in SETTINGS}


# -- the window -----------------------------------------------------------

class _Sampler:
    """Recovery's progress on the window's clock: the daemons' counters
    every `SAMPLE_EVERY_S`."""

    def __init__(self, state: dict):
        self.state = state
        self.samples = []               # (perf_counter, sums)
        self._last = 0.0

    def sample(self, force: bool = False) -> None:
        now = time.perf_counter()
        if not force and now - self._last < SAMPLE_EVERY_S:
            return
        self._last = now
        self.samples.append((now, read_counters(self.state)))

    def at(self, t: float) -> dict:
        """The newest sums sampled no later than `t`."""
        before = [c for at, c in self.samples if at <= t]
        return before[-1] if before else self.samples[0][1]


def window(state: dict, seconds: float, tick, log) -> dict:
    """`rados.window`, with recovery's counters sampled while it runs:
    the window's rise is read at its close, not after the loops' last
    ops came home, and by slices of `SLICE_S`."""
    sampler = state["sampler"] = _Sampler(state)
    sampler.sample(force=True)
    before = sampler.samples[0][1]
    pending_at_open = gauges(state)["recover_programs_pending"]

    def tick_and_sample():
        tick()
        sampler.sample()
    run = rados.window(state, seconds, tick_and_sample, log)
    sampler.sample(force=True)
    close = sampler.at(run["t1"])
    run["counters"].update({key: close[key] - before[key]
                            for key in COUNTERS})
    at_failure = state["counters_at_failure"]
    n_slices = max(1, int(round((run["t1"] - run["t0"]) / SLICE_S)))
    width = (run["t1"] - run["t0"]) / n_slices
    marks = [sampler.at(run["t0"] + i * width)["recovered_objects"]
             for i in range(n_slices)] + [close["recovered_objects"]]
    g = state["config"]["geometry"]
    run["recovery"] = {
        # what the `recovery.*` readers divide by
        "helper_bytes_an_object": g["k"] * (g["object_bytes"] // g["k"]),
        "work_bytes_an_object": recovery_work_bytes(state["config"], 1),
        "rebuilt_before_window": before["recovered_objects"]
        - at_failure["recovered_objects"],
        "rebuilt_at_close": close["recovered_objects"]
        - at_failure["recovered_objects"],
        "rebuilt_in_window": run["counters"]["recovered_objects"],
        "rebuilt_by_slice": [b - a for a, b in zip(marks, marks[1:])],
        "programs_pending_at_open": pending_at_open,
        "window_s": run["t1"] - run["t0"]}
    notes = run["notes"]
    notes["rebuilt_before_window"] = run["recovery"]["rebuilt_before_window"]
    notes["rebuilt_in_window"] = run["recovery"]["rebuilt_in_window"]
    notes["rebuilt_by_slice"] = run["recovery"]["rebuilt_by_slice"]
    log(f"rados_recovering window: counters "
        f"{ {key: run['counters'][key] for key in COUNTERS} }; "
        f"{run['recovery']}")
    return run


def pool_state(state: dict) -> dict:
    """The pool as the monitors' committed map and the primaries hold
    it: who is down, who is out, every PG's acting set and whether its
    primary serves that set (whether anything is left to rebuild is
    `wait_for_clean`'s to say: `finish`)."""
    failure, cluster = state["failure"], state["cluster"]
    pg_num = state["config"]["cluster"]["pg_num"]
    osdmap = max((m.osdmap for m in cluster.mons if m.osdmap is not None),
                 key=lambda m: m.epoch)
    acting = _acting_by_pg(osdmap, pg_num)
    unclean = []
    for pg, members in acting.items():
        d = cluster.osds.get(members[0])
        be = None if d is None or d._stop.is_set() else d.backends.get(pg)
        if be is None or [int(o) for o in be.acting] != members:
            unclean.append(pg)
    return {"victim": failure["victim"],
            "down": sorted(int(o) for o in cluster.osd_ids()
                           if not osdmap.osd_up[o]),
            "out": sorted(int(o) for o in cluster.osd_ids()
                          if osdmap.osd_weight[o] == 0),
            "acting": acting, "pgs_off_the_map": unclean,
            "others_suspected": others_suspected(state)}


# -- the comparison -------------------------------------------------------

class _Interned:
    """Rows that are byte for byte the same kept once: a pool of a
    thousand objects of 16 payloads holds 176 different rows, and the
    comparison wants every one of the eleven thousand."""

    def __init__(self):
        self._by_sum = {}

    def __call__(self, row):
        if row is None:
            return None
        same_sum = self._by_sum.setdefault(zlib.adler32(row), [])
        for kept in same_sum:
            if np.array_equal(kept, row):
                return kept
        same_sum.append(row)
        return row


def _stores_of(state: dict, acting: list, pg: int) -> list:
    """(store, collection) of each slot of the PG on the OSD that acts
    for it; None for a slot that names no OSD."""
    from ceph_tpu.osd.pgbackend import shard_cid
    osds = state["cluster"].osds
    return [(osds[osd].store, shard_cid(f"{POOL}.{pg}", shard))
            if osd in osds else None
            for shard, osd in enumerate(acting)]


def observe(state: dict, run: dict) -> dict:
    """What the run produced, once recovery has run to its end: the k+m
    rows and hinfo crcs of every object the pool was ever given (the
    backlog, the warm-up's and the leads' writes, the window's) as the
    stores of the PG's new acting set hold them, a seed-drawn sample
    read back through the client, for every (PG, payload) the k slots
    its lost row is to be decoded from (drawn from the seed among the
    slots the failure did not touch: not the program's helpers), the
    counters, and the pool's state."""
    config, wl = state["config"], state["workload"]
    pool = run["pool_at_end"]
    written = [(name, pay, "backlog") for name, pay in state["working_set"]]
    seen = {name for name, _, _ in written}
    with state["lock"]:
        loop_ops = [op for op in state["ops"]
                    if op["ok"] and op["kind"] == "write"]
    for op in loop_ops:
        if op["name"] in seen:
            continue
        seen.add(op["name"])
        origin = ("window" if run["t0"] <= op["start"] <= run["t1"]
                  else "before_out" if op["end"] <= state["t_out"]
                  else "set_up")
        written.append((op["name"], op["payload"], origin))
    rng = np.random.default_rng([state["seed"], 2])
    in_window = [i for i, w in enumerate(written) if w[2] == "window"]
    backs = set(rng.choice(len(written), min(wl["readback_objects"],
                                             len(written)),
                           replace=False).tolist())
    if in_window:
        backs |= {in_window[-1]} | set(rng.choice(
            in_window, min(wl["readback_objects"], len(in_window)),
            replace=False).tolist())
    repointed, lost_slot = (state["failure"]["repointed"],
                            state["failure"]["lost_slot"])
    k, n = config["geometry"]["k"], (config["geometry"]["k"]
                                     + config["geometry"]["m"])
    decode_from = {}
    for pg in sorted(repointed):
        moved = {r["slot"] for r in repointed[pg]}
        untouched = [s for s in range(n) if s not in moved]
        for pay in range(len(state["payloads"])):
            decode_from[(pg, pay)] = sorted(int(s) for s in rng.choice(
                untouched, k, replace=False))
    intern = _Interned()
    stores, objects = {}, []
    for i, (name, pay, origin) in enumerate(written):
        pg = rados._pg(state, name)
        if pg not in stores:
            stores[pg] = _stores_of(state, pool["acting"][pg], pg)
        rows, crcs = [], []
        for entry in stores[pg]:
            got = ([None], [None]) if entry is None \
                else rados._stored([entry], name)
            rows.append(intern(got[0][0]))
            crcs.append(got[1][0])
        obj = {"name": name, "payload": pay, "pg": pg, "origin": origin,
               "rows": rows, "crcs": crcs}
        if i in backs:
            back = rados._one_op(state, "read", name, pay, -3, keep=True)
            obj["readback"] = back.get("returned") if back["ok"] else None
        objects.append(obj)
    return {"objects": objects, "payloads": state["payloads"],
            "decode_from": decode_from,
            "acked_in_window": sum(
                1 for op in run["ops"] if op["ok"] and op["kind"] == "write"
                and op["start"] >= run["t0"]),
            "counters": run["counters"], "failed": run["failed"],
            "recovery": run["recovery"],
            "since_failure": run["since_failure"],
            "degraded_at_out": state["degraded_at_out"],
            "gauges": run["gauges_at_end"], "settings": run["settings"],
            "pool": pool,
            "map": {"victim": state["failure"]["victim"],
                    "repointed": repointed, "lost_slot": lost_slot}}


def compare(config: dict, workload: dict, ob: dict) -> list[dict]:
    """Each number beside its limit. Exact comparisons: the limit is 0."""
    g, stated = config["geometry"], config["failure"]
    settled = config["recovery"]
    n = g["k"] + g["m"]
    n_osds = config["cluster"]["n_osds"]
    matrix = gf256.reed_sol_van(g["k"], g["m"])
    payloads = ob["payloads"]
    want = recovered_pool.stripes(payloads, g["k"], g["m"],
                                  g["stripe_unit_bytes"])
    lost_slot = ob["map"]["lost_slot"]
    rows_wrong = crcs_wrong = missing = back_wrong = backs = 0
    rebuilt_wrong = rebuilt_crcs_wrong = rebuilt_rows = 0
    origins = collections.Counter()
    same = {}                    # a row kept once is compared once
    plain = {}                   # (pg, payload) -> (rebuilt row, its crc)
    for o in ob["objects"]:
        rows, crcs = want[o["payload"]]
        origins[o["origin"]] += 1
        for s in range(n):
            got = o["rows"][s] if s < len(o["rows"]) else None
            crc = o["crcs"][s] if s < len(o["crcs"]) else None
            missing += got is None
            key = (id(got), o["payload"], s)
            if got is not None and key not in same:
                same[key] = np.array_equal(got, rows[s])
            rows_wrong += got is None or not same[key]
            crcs_wrong += crc is None or int(crc) != int(crcs[s])
        if "readback" in o:
            backs += 1
            back_wrong += o["readback"] != payloads[o["payload"]]
        slot = lost_slot.get(o["pg"])
        if o["origin"] == "backlog" and slot is not None:
            # (b) the row on the new member against the plain decode of
            # k rows the failure did not touch, as this object's
            # neighbours on the survivors hold them
            there = ob["decode_from"][(o["pg"], o["payload"])]
            rebuilt_rows += 1
            if any(o["rows"][s] is None for s in there + [slot]):
                rebuilt_wrong += 1
                continue
            cls = (o["pg"], o["payload"]) + tuple(id(o["rows"][s])
                                                  for s in there)
            if cls not in plain:
                row = recovered_pool.rebuilt_rows(matrix, o["rows"], there,
                                                  [slot])[0]
                plain[cls] = row, int(crc32c.crc32c_rows(
                    recovered_pool.CRC_SEED, row[None, :])[0])
            row, crc = plain[cls]
            key = (id(o["rows"][slot]),) + cls
            if key not in same:
                same[key] = np.array_equal(row, o["rows"][slot])
            rebuilt_wrong += not same[key]
            rebuilt_crcs_wrong += int(o["crcs"][slot]) != crc
    c, since, pool = ob["counters"], ob["since_failure"], ob["pool"]
    rec, gauge = ob["recovery"], ob["gauges"]
    holed = [pg for pg, acting in pool["acting"].items()
             if recovered_pool.holes(acting, n_osds)]
    repointed_off = sum(
        1 for pg, moves in stated["repointed_by_pg"].items()
        if ob["map"]["repointed"].get(int(pg)) != moves)
    victim_acts = sum(pool["victim"] in acting
                      for acting in pool["acting"].values())
    settings_off = sorted(key for key, value in ob["settings"].items()
                          if str(value) != str(settled[key]))
    budget = (settled["osd_recovery_max_active"]
              * settled["osd_recovery_max_chunk"])
    per_grant = grant_objects(config)
    checks = [
        # (a) every acknowledged write, and everything else the pool was
        # given, on the new acting set
        check("stored_rows_wrong", int(rows_wrong), "<=", 0),
        check("stored_crcs_wrong", int(crcs_wrong), "<=", 0),
        check("shards_missing", int(missing), "<=", 0),
        check("window_writes_missing",
              ob["acked_in_window"] - origins["window"], "<=", 0),
        check("window_objects_compared", origins["window"], ">=", 1),
        check("readback_wrong", int(back_wrong), "<=", 0),
        check("objects_read_back", backs, ">=", 1),
        # (b) every backlog object's rebuilt row by another route
        check("rebuilt_rows_wrong", int(rebuilt_wrong), "<=", 0),
        check("rebuilt_crcs_wrong", int(rebuilt_crcs_wrong), "<=", 0),
        check("rebuilt_rows_compared", int(rebuilt_rows), ">=",
              sum(workload["backlog_objects_by_pg"].get(pg, 0)
                  for pg, moves in stated["repointed_by_pg"].items()
                  if any(r["lost"] for r in moves))),
        check("pgs_the_victim_acts_for", victim_acts, "<=", 0),
        # (c) recovery ran through the whole window and past its close
        check("slices_without_recovery",
              sum(1 for rise in rec["rebuilt_by_slice"] if rise < 1),
              "<=", 0),
        check("backlog_left_at_close",
              since["recovered_objects"] - rec["rebuilt_at_close"],
              ">=", 1),
        # (d) the reservation and the grant's budget held
        check("backfills_active_max", gauge["backfills_active_max"], "<=",
              settled["osd_max_backfills"]),
        check("grant_bytes_max", gauge["recover_grant_bytes_max"], "<=",
              budget),
        check("reservations_granted",
              since["backfill_reservations_granted"], ">=", 1),
        # (e) rebuilt on the device, by programs built ahead
        check("recover_launches", since["recover_launches"], ">=", 1),
        check("rebuilt_over_the_launches_room",
              max(0, since["recovered_objects"]
                  - per_grant * since["recover_launches"]), "<=", 0),
        check("recover_host_launches", since["recover_host_launches"],
              "<=", 0),
        check("host_decode_launches", since["host_decode_launches"],
              "<=", 0),
        check("host_encode_launches", c["host_encode_launches"], "<=", 0),
        check("device_write_launches", c["fused_write_launches"], ">=", 1),
        check("programs_pending_at_open", rec["programs_pending_at_open"],
              "<=", 0),
        check("rebuilt_while_down_and_in",
              ob["degraded_at_out"]["recovered_objects"]
              + ob["degraded_at_out"]["recover_programs_ready"], "<=", 0),
        # (f) the pool's state, on the map and the settings the file states
        check("pgs_with_a_hole", len(holed), "<=", 0),
        check("pgs_off_the_map", len(pool["pgs_off_the_map"]), "<=", 0),
        check("pool_clean", int(pool["clean"]), ">=", 1),
        check("osds_down_at_end", len(pool["down"]), "<=", 1),
        check("victim_down_at_end",
              int(pool["down"] == [pool["victim"]]), ">=", 1),
        check("victim_out_at_end",
              int(pool["out"] == [pool["victim"]]), ">=", 1),
        check("others_suspected", len(pool["others_suspected"]), "<=", 0),
        check("victim_off_file",
              abs(ob["map"]["victim"] - stated["victim"]), "<=", 0),
        check("repointed_pgs_off_file", repointed_off, "<=", 0),
        check("settings_off_file", len(settings_off), "<=", 0),
        # (g)
        check("ops_failed", ob["failed"], "<=", 0)]
    return checks


def finish(state: dict, run: dict, log) -> None:
    """Let recovery run to its end, then read the pool's state, the
    settings and the counters' rise since the failure onto `run`."""
    config, cluster = state["config"], state["cluster"]
    sampler = state["sampler"]
    t0 = time.perf_counter()
    deadline = t0 + config["failure"]["clean_timeout_s"]
    clean = False
    while not clean and time.perf_counter() < deadline:
        try:
            cluster.wait_for_clean(timeout=0.25)
            clean = True
        except TimeoutError:
            pass
        sampler.sample()
    sampler.sample(force=True)
    t1 = time.perf_counter()
    run["pool_at_end"] = dict(pool_state(state), clean=clean)
    run["since_failure"] = _since_failure(state)
    run["gauges_at_end"] = gauges(state)
    run["settings"] = settings(state)
    notes = run["notes"]
    notes["time_to_clean_s"] = round(t1 - state["t_out"], 3) if clean \
        else None
    notes["backlog_left_at_close"] = (
        run["since_failure"]["recovered_objects"]
        - run["recovery"]["rebuilt_at_close"])
    notes["rebuilt_since_failure"] = run["since_failure"]["recovered_objects"]
    notes["gauges"] = run["gauges_at_end"]
    log(f"rados_recovering: clean {clean} {t1 - run['t1']:.2f} s after the "
        f"close, {notes['time_to_clean_s']} s after the out mark; since "
        f"the failure {run['since_failure']}; {run['gauges_at_end']}; "
        f"settings {run['settings']}; pool {run['pool_at_end']}")


def verify(state: dict, run: dict, log) -> list[dict]:
    finish(state, run, log)
    t1 = time.perf_counter()
    ob = observe(state, run)
    t2 = time.perf_counter()
    # the reference needs no cluster, and its byte loops share the
    # interpreter with every daemon's threads: stop them first
    rados._stop_cluster(state)
    t3 = time.perf_counter()
    checks = compare(state["config"], state["workload"], ob)
    t4 = time.perf_counter()
    run["notes"]["compare_s"] = [round(t2 - t1, 3), round(t4 - t3, 3)]
    log(f"rados_recovering verify: {len(ob['objects'])} objects' rows read "
        f"in {t2 - t1:.2f} s, the cluster stopped in {t3 - t2:.2f} s, the "
        f"reference and the comparison {t4 - t3:.2f} s")
    return checks


def close(state: dict, log) -> None:
    rados._stop_cluster(state)
