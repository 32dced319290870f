"""Driver `rados`: closed client loops on a served EC pool, as
`rados bench -t <n> write | seq` drives one.

From the program it takes `StandaloneCluster`, its `client()` and that
client's `write` / `read`, the daemons' `ec` counters, and (for the
comparison, after the window) the daemons' stores. Names, payloads,
sampling and the comparison are the benchmark's own.

Objects are named as `rados bench` names them, one counter shared by
all the loops (`benchmark_data_bench_object<N>`), and fall on the PG
their name hashes to: some primaries get more than others, as in a real
run. The names are the same for every seed, so every seed loads the PGs
alike; the seed gives the bytes and the order (writes: each block of 64
names shuffled; reads: where in the name order the loops start). The loops start in the warm-up and run on into the window
without a pause, so the window opens and closes on 16 ops in flight:
bytes count for ops that complete inside it, latency for ops issued
inside it. The window opens on a whole pool: where the boot or the
warm-up leaves a daemon suspecting a live peer, `warm` boots again.
"""

from __future__ import annotations

import collections
import itertools
import struct
import tempfile
import threading
import time

import numpy as np

from bench.checks import check
from bench.reference import crc32c, gf256

POOL = 1
HINFO_KEY = "hinfo_key"
CRC_SEED = 0xFFFFFFFF
COUNTERS = ("fused_write_launches", "encode_launches", "decode_launches",
            "host_encode_launches", "program_cache_misses")
TIMES = ("encode_time", "decode_time")
SHUFFLE = 64                 # names a seed reorders among themselves
HUNG_AFTER_S = 60.0          # how long past the close an answer is waited for
SET_UP_TRIES = 5             # boots until the pool is whole, then no more


# -- shapes -------------------------------------------------------------

def work_bytes(config: dict, workload: dict, n_ops: int) -> float:
    """Bytes the algorithm must move through device memory for n_ops.
    A write encodes one object: k rows read, m rows written, and one crc
    word per csum block of all k+m rows written. A healthy read passes
    the k data rows once for their crc."""
    g = config["geometry"]
    row = g["object_bytes"] // g["k"]
    if workload["op"] == "write":
        per_op = ((g["k"] + g["m"]) * row
                  + (g["k"] + g["m"]) * (row // g["csum_block_bytes"]) * 4)
    else:
        per_op = g["k"] * row
    return float(n_ops) * per_op


def data_rows(config: dict, payload: bytes) -> np.ndarray:
    """(k, row) data rows of one object: Ceph's striping, `stripe_unit`
    bytes to each shard in turn."""
    g = config["geometry"]
    unit = g["stripe_unit_bytes"]
    flat = np.frombuffer(payload, np.uint8)
    return flat.reshape(-1, g["k"], unit).transpose(1, 0, 2).reshape(
        g["k"], -1)


# -- set-up -------------------------------------------------------------

def _pg(state: dict, name: str) -> int:
    return state["client"].osdmap.object_to_pg(POOL, name)[1]


def _per_pg(state: dict, names: list) -> dict:
    """How many of `names` fall on each PG, for the log."""
    return dict(sorted(collections.Counter(
        _pg(state, name) for name in names).items()))


def _name_on_pg(state: dict, pg: int, stem: str) -> str:
    """A name that hashes to `pg`: only the cold client's first pass
    uses it, to reach every primary once from one thread."""
    salt = 0
    while _pg(state, f"{stem}.{salt}") != pg:
        salt += 1
    return f"{stem}.{salt}"


def object_name(n: int) -> str:
    """`rados bench` names its objects benchmark_data_<host>_<pid>_object<N>."""
    return f"benchmark_data_bench_object{n}"


def suspected(state: dict) -> list:
    """[daemon, peer] for every OSD that a daemon holds for unreachable
    while the map says it is up. Such a daemon writes round that peer:
    it acks objects with k+m-1 shards, and the program never takes the
    suspicion back while the map stands (PERF.md, Open questions). The
    cell is a pool of `n_osds` that are all up, so set-up does not hand
    one over that suspects any."""
    return sorted([d.osd_id, int(peer)]
                  for d in state["cluster"].osds.values()
                  for peer in tuple(d.suspect))


def _boot(state: dict, log) -> None:
    """The cluster, clean by the program's own gate, and a client."""
    from ceph_tpu.osd.standalone import StandaloneCluster
    t0 = time.perf_counter()
    config = state["config"]
    c, g = config["cluster"], config["geometry"]
    state["store_dir"] = tempfile.TemporaryDirectory(prefix="bench-tin-")
    state.update(cluster=None, threads=[], stop=threading.Event(), ops=[],
                 next_object=itertools.count(), in_flight={})
    state["cluster"] = cluster = StandaloneCluster(
        n_osds=c["n_osds"], pg_num=c["pg_num"], profile=config["profile"],
        store=c["store"], store_dir=state["store_dir"].name,
        cephx=c["cephx"], secret=b"bench cluster key" * 2,
        hb_interval=c["hb_interval_s"], hb_grace=c["hb_grace_s"],
        chunk_size=g["stripe_unit_bytes"])
    cluster.wait_for_clean(timeout=120)
    state["client"] = cluster.client()
    log(f"rados boot: {c['n_osds']} osds, {c['pg_num']} pgs in "
        f"{time.perf_counter() - t0:.2f} s, suspected {suspected(state)}")


def setup(config: dict, workload: dict, seed: int, log) -> dict:
    from ceph_tpu import native
    # the stores' and frames' host crc32c: built once per checkout, not
    # raced by fifteen daemons on first use
    native.build()
    g = config["geometry"]
    state = {"config": config, "workload": workload, "seed": seed,
             "store_dir": None, "cluster": None, "threads": [],
             "stop": threading.Event(), "lock": threading.Lock(),
             "health": {"boots": 0}}
    t1 = time.perf_counter()
    rng = np.random.default_rng(seed)
    state["payloads"] = [
        rng.integers(0, 256, g["object_bytes"], dtype=np.uint8).tobytes()
        for _ in range(workload["distinct_payloads"])]
    state["payload_order"] = [int(p) for p in
                              rng.permutation(len(state["payloads"]))]
    if workload["op"] == "read":
        n_set = workload["working_set_objects"]
        state["working_set"] = [_entry(state, j) for j in range(n_set)]
        state["read_from"] = int(rng.integers(n_set))
    log(f"rados data: {len(state['payloads'])} payloads in "
        f"{time.perf_counter() - t1:.2f} s")
    return state


def _entry(state: dict, n: int) -> tuple[str, int]:
    """(name, payload index) of object number n."""
    pay = state["payload_order"]
    return object_name(n), pay[n % len(pay)]


def _shuffled(state: dict, n: int) -> int:
    """The n-th object number a writer takes: every block of SHUFFLE
    numbers in an order drawn from the seed, so that every seed writes
    the same names, in another order."""
    block, i = divmod(n, SHUFFLE)
    perms = state.setdefault("perms", {})
    if block not in perms:
        perms[block] = np.random.default_rng(
            [state["seed"], 3, block]).permutation(SHUFFLE)
    return block * SHUFFLE + int(perms[block][i])


# -- the loops ------------------------------------------------------------

def _one_op(state: dict, kind: str, name: str, pay: int, loop: int,
            keep: bool = False) -> dict:
    """One client op, timed from the call to its return. A read's bytes
    are kept on the op only where `keep` says so."""
    client, payloads = state["client"], state["payloads"]
    start = time.perf_counter()
    state["in_flight"][loop] = (name, pay, start)
    got = None
    try:
        if kind == "write":
            client.write({name: payloads[pay]})
        else:
            got = client.read(name)
        ok, err = True, None
    except Exception as e:           # a failed op is counted, not hidden
        ok, err = False, repr(e)
    end = time.perf_counter()
    op = {"kind": kind, "name": name, "payload": pay,
          "start": start, "end": end, "ok": ok, "err": err,
          "bytes": len(payloads[pay]) if ok else 0, "loop": loop}
    if got is not None:
        op["returned_len"] = len(got)
        if keep:
            op["returned"] = got
    return op


def _loop(state: dict, i: int) -> None:
    """One of the closed loops: takes the next object number, as each of
    `rados bench`'s ops in flight does. A writer's number is new; a
    reader's wraps round the working set."""
    kind = state["workload"]["op"]
    wrap = state["workload"].get("working_set_objects")
    while not state["stop"].is_set():
        n = next(state["next_object"])
        name, pay = _entry(state, (state["read_from"] + n) % wrap
                           if kind == "read" else _shuffled(state, n))
        op = _one_op(state, kind, name, pay, i,
                     keep=kind == "read" and state["keep_reads"])
        with state["lock"]:
            state["ops"].append(op)


def read_counters(state: dict) -> dict:
    daemons = list(state["cluster"].osds.values())
    out = {k: sum(int(d.ec_perf.get(k)) for d in daemons) for k in COUNTERS}
    out["launch_seconds"] = sum(float(d.ec_perf.get(k)["sum"])
                                for d in daemons for k in TIMES)
    return out


def _first_of_each_pg(state: dict, entries: list) -> tuple[list, list]:
    """`entries` split into the first on each PG and the rest."""
    seen, first, rest = set(), [], []
    for entry in entries:
        pg = _pg(state, entry[0])
        (rest if pg in seen else first).append(entry)
        seen.add(pg)
    return first, rest


def _must(op: dict) -> None:
    if not op["ok"]:
        raise RuntimeError(f"set-up {op['kind']} of {op['name']} failed: "
                           f"{op['err']}")


def warm(state: dict, log) -> None:
    """Boot and warm-up, again from the start where they leave a daemon
    suspecting a peer: a stall of the host during the boot's peering (its
    probes wait 1 s) or a first op does that, and nothing but a new map
    takes it back."""
    health = state["health"]
    found = []
    for boot in range(1, SET_UP_TRIES + 1):
        health["boots"] = boot
        try:
            _boot(state, log)
            found = suspected(state)
            if not found:
                _warm_once(state, log)
                found = suspected(state)
        except BaseException:
            _stop_cluster(state)
            raise
        if not found:
            return
        log(f"rados set-up {boot}: daemons suspect live peers "
            f"{found}: the pool would write degraded; booting again")
        health.setdefault("suspected_in_set_up", []).append(found)
        _stop_cluster(state)
    raise RuntimeError(f"no whole pool after {SET_UP_TRIES} boots: {found}")


def _warm_once(state: dict, log) -> None:
    wl = state["workload"]
    t0 = time.perf_counter()
    state["keep_reads"] = False            # keep nothing before the window
    pgs = range(state["config"]["cluster"]["pg_num"])
    # first pass single-threaded, one op per PG: a cold Client shared by
    # concurrent callers fails to authorize (ISSUE 24; ROADMAP A3b)
    if wl["op"] == "write":
        for pg in pgs:
            _must(_one_op(state, "write",
                          _name_on_pg(state, pg, f"warm-{pg}"),
                          0, -1))
    else:
        first = _write_working_set(state, log)
        for name, pay in first:
            _must(_one_op(state, "read", name, pay, -1))
    log(f"rados warm: one {wl['op']} per pg in "
        f"{time.perf_counter() - t0:.2f} s")

    state["threads"] = [threading.Thread(target=_loop, args=(state, i),
                                         name=f"bench-loop-{i}", daemon=True)
                        for i in range(wl["loops"])]
    for t in state["threads"]:
        t.start()
    # the cell's own traffic, until no program has been compiled or
    # loaded for a few seconds
    t1 = time.perf_counter()
    misses, since = read_counters(state)["program_cache_misses"], t1
    while True:
        time.sleep(0.25)
        now = time.perf_counter()
        seen = read_counters(state)["program_cache_misses"]
        if seen != misses:
            misses, since = seen, now
        if (now - t1 >= wl["warm_min_s"]
                and now - since >= wl["warm_quiet_s"]) \
                or now - t1 >= wl["warm_max_s"]:
            break
    with state["lock"]:
        names = [op["name"] for op in state["ops"] if op["ok"]]
    served = sorted({_pg(state, name) for name in names})
    log(f"rados warm: loops ran {now - t1:.2f} s, {len(names)} ops on pgs "
        f"{served}, program_cache_misses {misses}")


def _write_working_set(state: dict, log) -> list:
    """The seq cell's set-up: the working set goes in through the same
    client, the first object of each PG from one thread (the client is
    cold), the rest from as many threads as the cell has loops. Returns
    those first objects."""
    from concurrent.futures import ThreadPoolExecutor
    t0 = time.perf_counter()
    first, rest = _first_of_each_pg(state, state["working_set"])

    def put(entry):
        _must(_one_op(state, "write", entry[0], entry[1], -2))
    for entry in first:
        put(entry)
    with ThreadPoolExecutor(state["workload"]["loops"]) as pool:
        list(pool.map(put, rest))
    log(f"rados working set: {len(state['working_set'])} objects written in "
        f"{time.perf_counter() - t0:.2f} s, objects per pg "
        f"{_per_pg(state, [name for name, _ in state['working_set']])}")
    return first


def window(state: dict, seconds: float, tick, log) -> dict:
    state["keep_reads"] = True
    before = read_counters(state)
    t0 = time.perf_counter()
    while True:
        tick()
        left = t0 + seconds - time.perf_counter()
        if left <= 0:
            break
        time.sleep(min(0.02, left))
    t1 = time.perf_counter()
    after = read_counters(state)
    state["stop"].set()
    # every op issued in the window is waited for: one that comes late is
    # late, not lost
    deadline = time.perf_counter() + HUNG_AFTER_S
    for t in state["threads"]:
        t.join(max(deadline - time.perf_counter(), 0.1))
    with state["lock"]:
        every = list(state["ops"])
    # a loop that never came back: its op in flight is a failed op, issued
    # in the window at the latest when it opened
    kind = state["workload"]["op"]
    hung = [i for i, t in enumerate(state["threads"]) if t.is_alive()]
    for i in hung:
        name, pay, start = state["in_flight"][i]
        every.append({"kind": kind, "name": name, "payload": pay,
                      "start": max(start, t0), "end": time.perf_counter(),
                      "ok": False, "err": "no answer a minute past the close",
                      "bytes": 0, "loop": i})
    ops = [op for op in every if op["end"] >= t0 and op["start"] <= t1]
    issued = [op for op in ops if op["start"] >= t0]
    done = [op for op in ops if op["ok"] and op["end"] <= t1]
    counters = {k: after[k] - before[k] for k in COUNTERS}
    counters["ec_launches"] = sum(counters[k] for k in COUNTERS[:3])
    counters["ec_launch_seconds"] = (after["launch_seconds"]
                                     - before["launch_seconds"])
    counters["ops_done"] = len(done)
    late = max((op["end"] - t1 for op in issued), default=0.0)
    log(f"rados window: {len(issued)} ops issued, {len(done)} completed in "
        f"{t1 - t0:.3f} s, last returned {late:.2f} s after the close, "
        f"{len(hung)} loops hung; ops per pg "
        f"{_per_pg(state, [op['name'] for op in issued])}; "
        f"counters {counters}")
    for op in ops:
        if not op["ok"]:
            log(f"rados failed op {op['name']}: {op['err']}")
    state["health"]["suspected_after_window"] = suspected(state)
    return {"ops": ops, "notes": state["health"], "t0": t0, "t1": t1, "window_s": t1 - t0,
            "attempted": len(issued),
            "failed": sum(not op["ok"] for op in issued),
            "counters": counters}


# -- the comparison -------------------------------------------------------

def _shard_stores(state: dict, pg: int):
    """(store, collection) of each of the PG's k+m shards, in shard
    order, on the OSDs that act for it."""
    from ceph_tpu.osd.pgbackend import shard_cid
    acting = state["client"].osdmap.pg_to_up_acting_osds(POOL, pg)[2]
    return [(state["cluster"].osds[osd].store,
             shard_cid(f"{POOL}.{pg}", shard))
            for shard, osd in enumerate(acting)]


def _stored(stores: list, name: str) -> tuple[list, list]:
    """The k+m rows and hinfo crcs of one object as the acting OSDs'
    stores hold them; None where a store has no such shard."""
    rows, crcs = [], []
    for store, cid in stores:
        try:
            rows.append(np.asarray(store.read(cid, name), np.uint8))
            raw = store.getattr(cid, name, HINFO_KEY)
            crcs.append(struct.unpack_from("<III", raw)[2])
        except KeyError:
            rows.append(None)
            crcs.append(None)
    return rows, crcs


def observe(state: dict, run: dict) -> dict:
    """What the timed path produced, as the comparison takes it: the
    stored shards of every object written (in the window, or the seq
    cell's working set), a sample of them read back (drawn from the
    seed, the last one in it), and the bytes every timed read returned.
    An object holds its payload's index; `payloads` holds the bytes."""
    wl = state["workload"]
    if wl["op"] == "write":
        written = [(op["name"], op["payload"]) for op in run["ops"]
                   if op["ok"] and op["kind"] == "write"
                   and op["start"] >= run["t0"]]
    else:
        written = list(state["working_set"])
    rng = np.random.default_rng([state["seed"], 2])
    n_pick = min(wl["readback_objects"], len(written))
    picks = (set(rng.choice(len(written), n_pick, replace=False).tolist())
             | ({len(written) - 1} if written else set()))
    stores = {}
    objects = []
    for i, (name, pay) in enumerate(written):
        pg = _pg(state, name)
        if pg not in stores:
            stores[pg] = _shard_stores(state, pg)
        rows, crcs = _stored(stores[pg], name)
        obj = {"name": name, "payload": pay, "rows": rows, "crcs": crcs}
        if i in picks:
            back = _one_op(state, "read", name, pay, -3, keep=True)
            obj["readback"] = back.get("returned") if back["ok"] else None
        objects.append(obj)
    reads = [{"name": op["name"], "payload": op["payload"],
              "returned": op["returned"]}
             for op in run["ops"] if "returned" in op]
    ob = {"objects": objects, "payloads": state["payloads"],
          "reads": reads, "counters": run["counters"],
          "failed": run["failed"]}
    if run.get("trace"):
        ob["traced"] = {
            "busy_s": run["trace"]["busy_s"],
            "least_s": (run["traced_work_bytes"]
                        / run["peaks"]["hbm_bytes_per_s"])}
    return ob


def reference_rows(config: dict, payload: bytes, matrix) -> np.ndarray:
    """The k+m rows the stores have to hold for one object."""
    data = data_rows(config, payload)
    return np.concatenate([data, gf256.rs_encode(matrix, data)])


def compare(config: dict, workload: dict, ob: dict) -> list[dict]:
    """Each number beside its limit. Exact comparisons: the limit is 0."""
    g = config["geometry"]
    matrix = gf256.reed_sol_van(g["k"], g["m"])
    n = g["k"] + g["m"]
    payloads = ob["payloads"]
    want = {}                    # payload index -> (rows, crcs), made once
    for pay in sorted({o["payload"] for o in ob["objects"]}):
        rows = reference_rows(config, payloads[pay], matrix)
        want[pay] = rows, crc32c.crc32c_rows(CRC_SEED, rows)
    rows_wrong = crcs_wrong = missing = back_wrong = backs = 0
    for o in ob["objects"]:
        rows, crcs = want[o["payload"]]
        for s in range(n):
            got = o["rows"][s] if s < len(o["rows"]) else None
            crc = o["crcs"][s] if s < len(o["crcs"]) else None
            missing += got is None
            rows_wrong += got is None or not np.array_equal(got, rows[s])
            crcs_wrong += crc is None or int(crc) != int(crcs[s])
        if "readback" in o:
            backs += 1
            back_wrong += o["readback"] != payloads[o["payload"]]
    reads_wrong = sum(1 for r in ob["reads"]
                      if r["returned"] != payloads[r["payload"]])
    c = ob["counters"]
    read = workload["op"] == "read"
    checks = [
        check("stored_rows_wrong", int(rows_wrong), "<=", 0),
        check("stored_crcs_wrong", int(crcs_wrong), "<=", 0),
        check("shards_missing", int(missing), "<=", 0),
        check("readback_wrong", int(back_wrong), "<=", 0),
        check("objects_compared", len(ob["objects"]), ">=", 1),
        check("objects_read_back", backs, ">=", 1)]
    if read:
        checks += [
            check("timed_reads_wrong", reads_wrong, "<=", 0),
            check("timed_reads_compared", len(ob["reads"]), ">=", 1)]
    checks += [
        check("ops_failed", ob["failed"], "<=", 0),
        check("host_encode_launches", c["host_encode_launches"], "<=", 0)]
    if not read:
        checks.append(check("device_launches", c["fused_write_launches"],
                            ">=", 1))
    elif "traced" in ob:
        # no counter of the program counts the read path's device launch
        # (`decode_launches` counts a pass-through), so only a traced run
        # can hold the device to its work: busy for no less than the
        # least time the reads that completed there need
        t = ob["traced"]
        checks.append(check("device_busy_s_traced", t["busy_s"], ">=",
                            t["least_s"]))
    return checks


def verify(state: dict, run: dict, log) -> list[dict]:
    t0 = time.perf_counter()
    ob = observe(state, run)
    t1 = time.perf_counter()
    checks = compare(state["config"], state["workload"], ob)
    absent = collections.Counter(
        (_pg(state, o["name"]), shard) for o in ob["objects"]
        for shard, row in enumerate(o["rows"]) if row is None)
    if absent:
        log(f"rados verify: objects without a shard, by (pg, shard): "
            f"{dict(sorted(absent.items()))}; acting by pg "
            f"{ {pg: state['client'].osdmap.pg_to_up_acting_osds(POOL, pg)[2] for pg in sorted({p for p, _ in absent})} }; "
            f"suspected [daemon, peer] {suspected(state)}")
    log(f"rados verify: {len(ob['objects'])} objects "
        f"({t1 - t0:.2f} s to read them), {len(ob['reads'])} timed reads, "
        f"reference {time.perf_counter() - t1:.2f} s")
    return checks


def _stop_cluster(state: dict) -> None:
    state["stop"].set()
    for t in state["threads"]:
        t.join(10)
    if state["cluster"] is not None:
        state["cluster"].shutdown()
        state["cluster"] = None
    if state["store_dir"] is not None:
        state["store_dir"].cleanup()
        state["store_dir"] = None


def close(state: dict, log) -> None:
    _stop_cluster(state)
