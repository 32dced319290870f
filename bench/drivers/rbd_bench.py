"""Driver `rbd_bench`: closed loops of small random overwrites on an RBD
image whose data objects live on a served EC pool, as
`rbd bench --io-type write --io-pattern rand` drives one.

From the program it takes `StandaloneCluster`, its `client()` and that
client's `write_at` (the timed op), `write` (the fill) and `read` (the
comparison), the daemons' `ec` counters and, after the window, the
daemons' stores. From `bench.drivers.rados` it takes, unchanged, the
payloads (`setup`), the boot (`_boot`, `suspected`, `_stop_cluster`), the
writing of whole objects (`_write_working_set`), the window and the
stored rows of an object (`_shard_stores`, `_stored`); nothing of it is
patched. What it adds is what the deployment adds: the image (names as
librbd's, offsets to objects), the op, a warm-up that meets every delta
program, counters of the RMW path, and a comparison against a block
device (`bench/reference/block_image.py`).

Set-up, in order: boot and `wait_for_clean`; the image's objects written
whole through the client from seeded payloads; `wait_for_clean`; one
4 KiB `write_at` into every (PG, data column) pair from one thread; then
the loops, until no program of any layer has been compiled or loaded for
`warm_quiet_s`. Every write since the fill is part of the image's
history. After the window `verify` reads the image back and the stores'
rows, stops the cluster (the reference's byte loops would share the
interpreter with every daemon's threads), and only then compares. A
program that cannot tell a device delta launch from a host
one (`rmw_host_delta_launches`) cannot show the configuration's
guarantee (c): `setup` says so and exits at once, before anything boots.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.checks import check
from bench.drivers import rados
from bench.reference import block_image, crc32c, gf256

JOURNAL_OBJ = "__stripe_journal__"
JOURNAL_APPLIED = b"applied"
COUNTERS = ("rmw_ops", "rmw_delta_launches", "rmw_host_delta_launches",
            "rmw_full_fallbacks", "rmw_shard_ios", "rmw_fetch_waves",
            "journal_entries", "host_encode_launches", "recover_launches",
            "program_cache_misses")
# the reference's crc is a byte loop in numpy that holds the interpreter
# for much of its time: 4 objects a pass and 3 passes at a time took 4-5 s
# for the image where 8 and 8 took 16 (PERF.md, PR 31)
PASS_OBJECTS = 4
PASSES_AT_A_TIME = 3


# -- shapes -------------------------------------------------------------

def work_bytes(config: dict, workload: dict, n_ops: int) -> float:
    """Bytes the algorithm must move through device memory for n_ops
    overwrites of one block: the data column's delta row in, m parity
    delta rows out, and one crc word for each of those 1 + m rows."""
    m = config["geometry"]["m"]
    return float(n_ops) * ((1 + m) * workload["io_size"] + (1 + m) * 4)


def object_name(config: dict, n: int) -> str:
    """librbd names an image's data objects rbd_data.<id>.<number>."""
    return f"rbd_data.{config['image']['id']}.{n:016x}"


def column_of(config: dict, offset: int) -> int:
    """The data column (shard row) that byte `offset` of an object is on."""
    g = config["geometry"]
    return offset // g["stripe_unit_bytes"] % g["k"]


# -- set-up -------------------------------------------------------------

def setup(config: dict, workload: dict, seed: int, log) -> dict:
    from ceph_tpu.osd.ecbackend import ec_perf_counters
    if "rmw_host_delta_launches" not in ec_perf_counters().dump():
        raise SystemExit(
            "rbd_bench: this program has no counter that tells a device "
            "delta launch from a host one (rmw_host_delta_launches): it "
            "cannot show that the timed writes were served on the device")
    if workload["op"] != "write_at" \
            or workload["io_size"] != config["image"]["block_bytes"]:
        raise SystemExit("rbd_bench: the cell overwrites single blocks of "
                         "the image; another op or size is another cell")
    state = rados.setup(config, workload, seed, log)
    pay = state["payload_order"]
    # object j of the image and the payload the fill gives it
    state["working_set"] = [(object_name(config, j), pay[j % len(pay)])
                            for j in range(config["image"]["objects"])]
    return state


def _one_write(state: dict, obj: int, offset: int, pay: int, cut: int,
               loop: int) -> dict:
    """One client `write_at`, timed from the call to its return."""
    size = state["workload"]["io_size"]
    name = state["working_set"][obj][0]
    block = state["payloads"][pay][cut:cut + size]
    start = time.perf_counter()
    state["in_flight"][loop] = (name, pay, start)
    try:
        state["client"].write_at(name, offset, block)
        ok, err = True, None
    except Exception as e:           # a failed op is counted, not hidden
        ok, err = False, repr(e)
    end = time.perf_counter()
    return {"kind": "write_at", "name": name, "object": obj,
            "offset": offset, "payload": pay, "cut": cut, "start": start,
            "end": end, "ok": ok, "err": err, "bytes": size if ok else 0,
            "loop": loop}


def _record(state: dict, op: dict) -> None:
    """Every write since the boot's fill is kept: `state["ops"]` is the
    image's history."""
    with state["lock"]:
        state["ops"].append(op)


def _loop(state: dict, i: int) -> None:
    """One of the closed loops: a block of the image, a payload and where
    to cut it, all from this loop's generator; the next op goes out when
    this one is acknowledged."""
    image, size = state["config"]["image"], state["workload"]["io_size"]
    per_object = image["object_bytes"] // size
    rng = np.random.default_rng([state["seed"], 5, i])
    n_pay = len(state["payloads"])
    while not state["stop"].is_set():
        block = int(rng.integers(image["blocks"]))
        pay = int(rng.integers(n_pay))
        cut = int(rng.integers(per_object)) * size
        _record(state, _one_write(state, block // per_object,
                                  block % per_object * size, pay, cut, i))


def read_counters(state: dict) -> dict:
    """The RMW path's counters summed over the daemons; each daemon's are
    read in one snapshot, so that counters it raises together (`rmw_ops`,
    `rmw_shard_ios`) are read together."""
    dumps = [d.ec_perf.dump() for d in state["cluster"].osds.values()]
    return {key: sum(int(dump[key]) for dump in dumps) for key in COUNTERS}


def _compiles() -> int:
    from ceph_tpu.utils.tracing import span_log
    return sum(1 for r in span_log() if r["name"] == "xla.compile")


def warm(state: dict, log) -> None:
    """Boot, fill, warm-up; again from the boot where a daemon comes to
    suspect a live peer on the way (as `rados.warm`)."""
    health = state["health"]
    found = []
    for boot in range(1, rados.SET_UP_TRIES + 1):
        health["boots"] = boot
        try:
            rados._boot(state, log)
            found = rados.suspected(state)
            if not found:
                _warm_once(state, log)
                found = rados.suspected(state)
        except BaseException:
            rados._stop_cluster(state)
            raise
        if not found:
            return
        log(f"rbd_bench set-up {boot}: daemons suspect live peers {found}; "
            f"booting again")
        health.setdefault("suspected_in_set_up", []).append(found)
        rados._stop_cluster(state)
    raise RuntimeError(f"no whole pool after {rados.SET_UP_TRIES} boots: "
                       f"{found}")


def _warm_once(state: dict, log) -> None:
    config, wl = state["config"], state["workload"]
    g, size = config["geometry"], wl["io_size"]
    state["keep_reads"] = False
    rados._write_working_set(state, log)           # the fill
    state["cluster"].wait_for_clean(timeout=120)
    state["counters_after_fill"] = read_counters(state)

    # every (PG, data column) pair once, from one thread: the eight delta
    # programs compile here, and every primary has met each
    t0 = time.perf_counter()
    rng = np.random.default_rng([state["seed"], 4])
    stripe = g["k"] * g["stripe_unit_bytes"]
    first = {}
    for obj, (name, _) in enumerate(state["working_set"]):
        first.setdefault(rados._pg(state, name), obj)
    missing = set(range(config["cluster"]["pg_num"])) - set(first)
    if missing:
        raise RuntimeError(f"no object of the image on pgs {sorted(missing)}")
    for pg, obj in sorted(first.items()):
        for col in range(g["k"]):
            offset = (int(rng.integers(g["object_bytes"] // stripe)) * stripe
                      + col * g["stripe_unit_bytes"])
            op = _one_write(state, obj, offset,
                            int(rng.integers(len(state["payloads"]))),
                            int(rng.integers(g["object_bytes"] // size))
                            * size, -1)
            rados._must(op)
            _record(state, op)
    log(f"rbd_bench warm: one write_at per (pg, column), "
        f"{len(state['ops'])} writes in {time.perf_counter() - t0:.2f} s,"
        f" {_compiles()} programs compiled or loaded so far, counters "
        f"{_since_fill(state)}")

    state["threads"] = [threading.Thread(target=_loop, args=(state, i),
                                         name=f"bench-loop-{i}", daemon=True)
                        for i in range(wl["loops"])]
    for t in state["threads"]:
        t.start()
    # the cell's own traffic, until no program of any layer has been
    # compiled or loaded for a few seconds
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
    log(f"rbd_bench warm: loops ran {now - t1:.2f} s, "
        f"{len(state['ops'])} writes so far, {compiles} programs "
        f"compiled or loaded so far")


def _since_fill(state: dict) -> dict:
    now, then = read_counters(state), state["counters_after_fill"]
    return {key: now[key] - then[key] for key in COUNTERS}


# -- the window -----------------------------------------------------------

def window(state: dict, seconds: float, tick, log) -> dict:
    """`rados.window`, with this deployment's counters read round it and
    the pool's state read as it closes."""
    from ceph_tpu.utils.tracing import span_log, span_log_dropped
    before, dropped = read_counters(state), span_log_dropped()
    run = rados.window(state, seconds, tick, log)
    after = read_counters(state)
    run["counters"].update({key: after[key] - before[key]
                            for key in COUNTERS})
    run["pool_at_close"] = pool_state(state)
    # how the rate held: ops completed in the first and last ten seconds
    third = min(10.0, seconds / 3)
    ends = [op["end"] for op in run["ops"]
            if op["ok"] and run["t0"] <= op["end"] <= run["t1"]]
    notes = run["notes"]
    notes["ops_first_s"] = [third, sum(e <= run["t0"] + third for e in ends)]
    notes["ops_last_s"] = [third, sum(e >= run["t1"] - third for e in ends)]
    # a log that wrapped is short of its oldest records: seen here, and
    # the readers of this driver's metrics then read nothing
    notes["span_log_dropped"] = span_log_dropped() - dropped
    notes["span_log_records"] = len(span_log(since=run["t0"]))
    log(f"rbd_bench window: counters "
        f"{ {key: run['counters'][key] for key in COUNTERS} }; pool at "
        f"close {run['pool_at_close']}; notes {notes}")
    return run


def pool_state(state: dict) -> dict:
    """Whether the pool is whole: the monitors' committed map, the
    daemons' suspicions and the primaries' recoveries."""
    cluster = state["cluster"]
    osdmap = max((m.osdmap for m in cluster.mons if m.osdmap is not None),
                 key=lambda m: m.epoch)
    return {"down": sorted(int(o) for o in cluster.osd_ids()
                           if not osdmap.osd_up[o]),
            "suspected": rados.suspected(state),
            "pgs_recovering": sum(len(d._recovering)
                                  for d in cluster.osds.values())}


# -- the comparison -------------------------------------------------------

def _journal(state: dict) -> dict:
    """What every shard's stripe journal holds once the loops have
    stopped: the intents left (any key but the watermark) and how many
    shards carry a watermark at all."""
    left, marks = [], 0
    for pg in range(state["config"]["cluster"]["pg_num"]):
        for shard, (store, cid) in enumerate(rados._shard_stores(state, pg)):
            if not store.exists(cid, JOURNAL_OBJ):
                continue
            for key, _ in store.omap_iter(cid, JOURNAL_OBJ):
                if bytes(key) == JOURNAL_APPLIED:
                    marks += 1
                else:
                    left.append([pg, shard, bytes(key).decode()])
    return {"intents_left": left, "shards_with_watermark": marks}


def observe(state: dict, run: dict) -> dict:
    """What the timed path produced, as the comparison takes it: every
    object of the image read back through the client, its k+m rows and
    hinfo crcs as the acting OSDs' stores hold them, every shard's stripe
    journal, the counters and the pool's state at the close; beside it
    the history (the fill and every write since) for the reference."""
    client = state["client"]
    objects, stores = [], {}
    for obj, (name, _) in enumerate(state["working_set"]):
        pg = rados._pg(state, name)
        if pg not in stores:
            stores[pg] = rados._shard_stores(state, pg)
        try:
            back = client.read(name)
        except Exception:            # counted as a wrong object
            back = None
        rows, crcs = rados._stored(stores[pg], name)
        objects.append({"name": name, "pg": pg, "readback": back,
                        "rows": rows, "crcs": crcs})
    with state["lock"]:
        history = list(state["ops"])
    return {"objects": objects, "payloads": state["payloads"],
            "fill": [pay for _, pay in state["working_set"]],
            "history": history, "journal": _journal(state),
            "counters": run["counters"], "failed": run["failed"],
            "pool": run["pool_at_close"]}


def reference_image(config: dict, ob: dict) -> tuple[np.ndarray, dict]:
    """The block device after the history, and its racing blocks."""
    image = block_image.filled(ob["payloads"], ob["fill"])
    racing = block_image.replay(image, ob["payloads"], ob["history"],
                                config["image"]["block_bytes"])
    return image, racing


def reference_shards(config: dict, image: np.ndarray, matrix
                     ) -> tuple[np.ndarray, np.ndarray]:
    """What the stores have to hold for `image`: (objects, k+m, row)
    rows, each object striped `stripe_unit` bytes a shard and encoded, and
    their (objects, k+m) hinfo crcs. A few objects a pass and a few
    passes at a time: numpy lets go of the interpreter."""
    g = config["geometry"]
    k = g["k"]
    rows = np.empty((len(image), k + g["m"], g["shard_row_bytes"]), np.uint8)
    crcs = np.empty(rows.shape[:2], np.uint32)

    def one_pass(at: int) -> None:
        part = rows[at:at + PASS_OBJECTS]
        for i, obj in enumerate(image[at:at + PASS_OBJECTS]):
            part[i, :k] = rados.data_rows(config, obj.tobytes())
            part[i, k:] = gf256.rs_encode(matrix, part[i, :k])
        crcs[at:at + PASS_OBJECTS] = crc32c.crc32c_rows(
            rados.CRC_SEED, part.reshape(-1, part.shape[-1])
        ).reshape(part.shape[:2])
    with ThreadPoolExecutor(PASSES_AT_A_TIME) as pool:
        list(pool.map(one_pass, range(0, len(image), PASS_OBJECTS)))
    return rows, crcs


def compare(config: dict, workload: dict, ob: dict) -> list[dict]:
    """Each number beside its limit. Exact comparisons: the limit is 0."""
    g, block = config["geometry"], config["image"]["block_bytes"]
    k, n = g["k"], g["k"] + g["m"]
    matrix = gf256.reed_sol_van(g["k"], g["m"])
    image, racing = reference_image(config, ob)
    zeros = np.zeros(g["object_bytes"], np.uint8)
    whole = [o["readback"] is not None
             and len(o["readback"]) == g["object_bytes"]
             for o in ob["objects"]]
    back = np.stack([np.frombuffer(o["readback"], np.uint8) if ok else zeros
                     for o, ok in zip(ob["objects"], whole)])
    # (a) what the client reads; a racing block may hold either write,
    # and the stores are then held to the one that was read
    settled = block_image.settle(image, racing, back, block)
    blocks_wrong = block_image.blocks_differing(image, back, block)
    # (b) the rows the stores have to hold for that image
    want, want_crcs = reference_shards(config, image, matrix)
    data_wrong = parity_wrong = crcs_wrong = missing = 0
    for i, o in enumerate(ob["objects"]):
        for s in range(n):
            got = o["rows"][s] if s < len(o["rows"]) else None
            crc = o["crcs"][s] if s < len(o["crcs"]) else None
            missing += got is None
            wrong = got is None or not np.array_equal(got, want[i, s])
            data_wrong += wrong and s < k
            parity_wrong += wrong and s >= k
            crcs_wrong += crc is None or int(crc) != int(want_crcs[i, s])
    c, pool, journal = ob["counters"], ob["pool"], ob["journal"]
    acked = sum(1 for w in ob["history"] if w["ok"])
    checks = [
        # (a)
        check("image_blocks_wrong", int(blocks_wrong), "<=", 0),
        check("objects_not_read_back", whole.count(False), "<=", 0),
        check("objects_compared", len(ob["objects"]), ">=",
              config["image"]["objects"]),
        check("writes_in_history", acked, ">=", 1),
        # (b)
        check("stored_data_rows_wrong", int(data_wrong), "<=", 0),
        check("stored_parity_rows_wrong", int(parity_wrong), "<=", 0),
        check("stored_crcs_wrong", int(crcs_wrong), "<=", 0),
        check("shards_missing", int(missing), "<=", 0),
        check("rows_compared", len(ob["objects"]) * n, ">=",
              config["image"]["objects"] * n),
        # (c)
        check("rmw_ops", c["rmw_ops"], ">=", 1),
        check("rmw_ops_off_acked", abs(c["rmw_ops"] - c["ops_done"]), "<=",
              2 * workload["loops"]),
        check("rmw_shard_ios_off_1_plus_m",
              abs(c["rmw_shard_ios"] - (1 + g["m"]) * c["rmw_ops"]),
              "<=", 0),
        check("rmw_full_fallbacks", c["rmw_full_fallbacks"], "<=", 0),
        check("rmw_host_delta_launches", c["rmw_host_delta_launches"],
              "<=", 0),
        check("host_encode_launches", c["host_encode_launches"], "<=", 0),
        check("rmw_delta_launches", c["rmw_delta_launches"], ">=", 1),
        # (d)
        check("journal_intents_left", len(journal["intents_left"]), "<=", 0),
        check("journal_watermarks", journal["shards_with_watermark"],
              ">=", 1 + g["m"]),
        # (e)
        check("osds_down_at_close", len(pool["down"]), "<=", 0),
        check("osds_suspected_at_close", len(pool["suspected"]), "<=", 0),
        check("pgs_recovering", pool["pgs_recovering"], "<=", 0),
        check("recover_launches", c["recover_launches"], "<=", 0),
        # (f)
        check("ops_failed", ob["failed"], "<=", 0),
        # seen, not limited: blocks two writes in flight together met,
        # and how many of them hold the write acknowledged first
        check("racing_blocks", len(racing), ">=", 0),
        check("racing_blocks_settled", settled, ">=", 0)]
    return checks


def verify(state: dict, run: dict, log) -> list[dict]:
    t0 = time.perf_counter()
    ob = observe(state, run)
    since_fill = _since_fill(state)
    # the reference needs no cluster, and its byte loops share the
    # interpreter with every daemon's threads: stop them first
    t1 = time.perf_counter()
    rados._stop_cluster(state)
    t2 = time.perf_counter()
    checks = compare(state["config"], state["workload"], ob)
    t3 = time.perf_counter()
    seen = {c["name"]: c["value"] for c in checks
            if c["name"] in ("racing_blocks", "racing_blocks_settled",
                             "writes_in_history")}
    state["health"].update(seen, compare_s=[round(t1 - t0, 3),
                                            round(t3 - t2, 3)])
    log(f"rbd_bench verify: {len(ob['objects'])} objects read back and "
        f"their rows read from the stores in {t1 - t0:.2f} s, the cluster "
        f"stopped in {t2 - t1:.2f} s, the reference and the comparison "
        f"{t3 - t2:.2f} s; {seen}; since the fill {since_fill}")
    return checks


def close(state: dict, log) -> None:
    rados._stop_cluster(state)
