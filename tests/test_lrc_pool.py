"""An LRC pool (k=8 m=4 l=3: four local groups of four) served end to
end: its writes take one fused device launch (the layers' generator
composed on the host, then the crcs of all 16 rows), and when an OSD is
out for good each PG rebuilds the lost row from the 3 other members of
its local group.

Wire tier at a small size: 64 KiB objects (rows of 8 KiB), 17 OSDs (one
to spare), 2 PGs, cephx, TinStore, seeded. One writer runs while a
non-primary OSD that holds a data slot is stopped, marked down and out.
Every object's 16 rows on the *new* acting set are held to the
benchmark's plain reference (`bench/reference/lrc_codeword.py`); each
rebuilt object pulled exactly 3 rows, through local plans only. The
CRUSH case is the benchmark's map: 17 OSDs, 8 PGs, 100 tries, osd.4
out."""

import json
import os
import threading
import time

import numpy as np
import pytest

from bench.reference import lrc_codeword, recovered_pool
from ceph_tpu.chaos import load_factor
from ceph_tpu.crush.map import Tunables, build_hierarchy, ec_rule
from ceph_tpu.ec.registry import factory
from ceph_tpu.osd.ecbackend import ECBackend, shard_cid
from ceph_tpu.osd.osdmap import OSDMap, PGPool
from ceph_tpu.osd.standalone import StandaloneCluster

_LF = load_factor()
K, M, L = 8, 4, 3
N = 16
UNIT = 256
SIZE = 64 << 10
ROW = SIZE // K
PROFILE = f"plugin=lrc k={K} m={M} l={L}"
DATA_SLOTS = [2, 3, 6, 7, 10, 11, 14, 15]
BACKLOG = 24
COUNTERS = ("recovered_objects", "recover_wire_bytes",
            "recover_helper_reads", "recover_host_launches",
            "host_decode_launches", "recover_launches",
            "planner_local_plans", "planner_full_plans")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def live(c):
    return [d for d in c.osds.values() if not d._stop.is_set()]


def counter(c, key):
    return sum(int(d.ec_perf.get(key)) for d in live(c))


def acting_of(osdmap, pg_num):
    return {ps: [int(o) for o in osdmap.pg_to_up_acting_osds(1, ps)[2]]
            for ps in range(pg_num)}


def mon_map(c):
    return max((m.osdmap for m in c.mons if m.osdmap is not None),
               key=lambda m: m.epoch)


def stored(c, acting, ps, name):
    import struct
    rows, crcs = [], []
    for shard, osd in enumerate(acting):
        store, cid = c.osds[osd].store, shard_cid(f"1.{ps}", shard)
        try:
            rows.append(np.asarray(store.read(cid, name), np.uint8))
            crcs.append(struct.unpack_from(
                "<III", store.getattr(cid, name, "hinfo_key"))[2])
        except KeyError:
            rows.append(None)
            crcs.append(None)
    return rows, crcs


@pytest.fixture(scope="module")
def device_path():
    """The fused device programs, as on the chip: no native host crc."""
    from ceph_tpu.osd import ecbackend
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        yield


def suspected(c):
    """[daemon, peer] for every live peer a daemon holds for unreachable."""
    return sorted([d.osd_id, int(p)] for d in live(c) for p in d.suspect)


def whole_pool(tmp_path_factory, payloads, tries=3):
    """A pool whose backlog every shard holds: booted and written again
    where a daemon suspects a live peer, as the benchmark's drivers do
    (`bench/drivers/rados.py` `warm`)."""
    for _ in range(tries):
        c = StandaloneCluster(n_osds=N + 1, pg_num=2, profile=PROFILE,
                              chunk_size=UNIT, down_out_interval=600.0,
                              hb_interval=2.0, hb_grace=45.0, store="tin",
                              store_dir=str(tmp_path_factory.mktemp("tin")),
                              cephx=True, secret=b"lrc pool secret!" * 2)
        try:
            # 17 daemons and 3 monitors boot in one interpreter: under a
            # loaded suite that takes minutes, not the 20 s it takes alone
            c.wait_for_clean(timeout=180 * _LF)
            cl = c.client()
            written = {}
            if not suspected(c):
                for i in range(BACKLOG):
                    cl.write({f"backlog-{i}": payloads[i % 8]})
                    written[f"backlog-{i}"] = i % 8
                c.wait_for_clean(timeout=30 * _LF)
            if written and not suspected(c):
                return c, cl, written
        except BaseException:
            c.shutdown()
            raise
        c.shutdown()
    raise RuntimeError(f"no whole pool in {tries} boots")


@pytest.fixture(scope="module")
def recovered(device_path, tmp_path_factory):
    """One pool: a backlog written healthy, a writer started, a
    non-primary OSD that holds a data slot stopped, marked down and out,
    the pool rebuilt to clean. No profiler session is live."""
    rng = np.random.default_rng(4021)
    payloads = [rng.integers(0, 256, SIZE, np.uint8).tobytes()
                for _ in range(8)]
    # the fused write for 64 KiB objects, built before any client op
    ECBackend(PROFILE, "0.0", list(range(N)), chunk_size=UNIT).write_objects(
        {"warm": np.zeros(SIZE, np.uint8)})
    c, cl, written = whole_pool(tmp_path_factory, payloads)
    try:
        old = acting_of(mon_map(c), c.pg_num)
        primaries = {a[0] for a in old.values()}
        victim = max((o for o in c.osd_ids() if o not in primaries),
                     key=lambda o: (sum(o in [a[s] for s in DATA_SLOTS]
                                        for a in old.values()), -o))
        stop, lock, failed = threading.Event(), threading.Lock(), []

        def loop():
            n = 0
            while not stop.is_set():
                name, pay = f"writer-{n}", n % 8
                try:
                    cl.write({name: payloads[pay]})
                    with lock:
                        written[name] = pay
                except Exception as e:   # noqa: BLE001 — counted
                    failed.append((name, repr(e)))
                n += 1
        writer = threading.Thread(target=loop, daemon=True)
        writer.start()
        time.sleep(0.3)
        before = {key: counter(c, key) for key in COUNTERS}
        writes_before = counter(c, "fused_write_launches")
        c.kill_osd(victim)
        cl.osd_down(victim, timeout=30 * _LF)
        c._wait(lambda: all(not d.osdmap.osd_up[victim] for d in live(c)),
                30 * _LF, f"every daemon's map shows osd.{victim} down")
        cl.osd_out(victim, timeout=30 * _LF)
        c._wait(lambda: all(d.osdmap.osd_weight[victim] == 0
                            for d in live(c)),
                30 * _LF, f"every daemon's map shows osd.{victim} out")
        c.wait_for_clean(timeout=120 * _LF)
        stop.set()
        writer.join(30)
        c.wait_for_clean(timeout=30 * _LF)
        yield {"c": c, "cl": cl, "payloads": payloads, "written": written,
               "old": old, "new": acting_of(mon_map(c), c.pg_num),
               "victim": victim, "failed": failed,
               "writes_before": writes_before,
               "rise": {key: counter(c, key) - was
                        for key, was in before.items()}}
    finally:
        c.shutdown()


def test_every_object_is_an_lrc_codeword_on_the_new_acting_set(recovered):
    c, cl = recovered["c"], recovered["cl"]
    assert recovered["failed"] == []
    assert len(recovered["written"]) > BACKLOG       # the writer wrote
    want = {}                    # payload -> its codeword's rows, crcs
    for pay, payload in enumerate(recovered["payloads"]):
        rows = lrc_codeword.codeword(payload, K, M, L, UNIT)
        want[pay] = rows, list(lrc_codeword.crcs(rows))
    wrong = []
    for name, pay in recovered["written"].items():
        ps = cl.osdmap.object_to_pg(1, name)[1]
        rows, crcs = stored(c, recovered["new"][ps], ps, name)
        bad = [p for p in range(N) if rows[p] is None
               or not np.array_equal(rows[p], want[pay][0][p])]
        if bad or list(crcs) != want[pay][1]:
            wrong.append((name, ps, bad))
    assert wrong == []


def test_the_victim_is_out_and_every_pg_whole(recovered):
    victim = recovered["victim"]
    assert [(d.osd_id, p) for d in live(recovered["c"]) for p in d.suspect
            if p != victim] == []
    moved = [r for ps, acting in recovered["new"].items()
             for r in recovered_pool.repointed(recovered["old"][ps], acting,
                                               victim)]
    for acting in recovered["new"].values():
        assert recovered_pool.holes(acting, N + 1) == []
        assert victim not in acting and len(set(acting)) == N
    assert any(r["lost"] and r["slot"] in DATA_SLOTS for r in moved)


def test_every_rebuilt_object_pulled_the_three_rows_of_its_group(recovered):
    rise = recovered["rise"]
    rebuilt = rise["recovered_objects"]
    lost_pgs = {ps for ps, a in recovered["old"].items()
                if recovered["victim"] in a}
    cl = recovered["cl"]
    assert rebuilt >= sum(1 for i in range(BACKLOG) if cl.osdmap.object_to_pg(
        1, f"backlog-{i}")[1] in lost_pgs) >= 1
    assert rise["recover_wire_bytes"] == rebuilt * L * ROW
    assert rise["recover_helper_reads"] == rebuilt * L
    assert rise["planner_local_plans"] >= len(lost_pgs)
    assert rise["planner_full_plans"] == 0
    assert rise["recover_launches"] >= 1
    assert rise["recover_host_launches"] == 0
    assert rise["host_decode_launches"] == 0


def test_each_write_batch_took_one_fused_launch(recovered):
    c = recovered["c"]
    assert counter(c, "fused_write_launches") - recovered["writes_before"] \
        >= 1
    assert counter(c, "fused_write_launches") >= len(recovered["written"])
    assert counter(c, "encode_launches") == 0
    assert counter(c, "host_encode_launches") == 0


def test_the_clients_read_back_what_they_wrote(recovered):
    cl, payloads = recovered["cl"], recovered["payloads"]
    names = sorted(recovered["written"])
    rng = np.random.default_rng(4022)
    for name in rng.choice(names, 8, replace=False):
        assert bytes(cl.read(str(name))) \
            == payloads[recovered["written"][str(name)]]


# -- CRUSH at the benchmark's map -----------------------------------------

def test_osd_4_out_is_the_configurations_map():
    crush = build_hierarchy(17, osds_per_host=1, hosts_per_rack=17)
    crush.tunables = Tunables(choose_total_tries=100)
    ec_rule(crush, 1, choose_type=1)
    osdmap = OSDMap(crush)
    osdmap.add_pool(PGPool(1, pg_num=8, size=N, min_size=K, crush_rule=1,
                           is_erasure=True))
    healthy = acting_of(osdmap, 8)
    with open(os.path.join(ROOT, "bench", "configs",
                           "rados_lrc_k8m4l3_17osd_1out.json")) as f:
        config = json.load(f)
    stated = config["failure"]
    victim = stated["victim"]
    coder = factory(PROFILE)
    assert coder.get_chunk_mapping()[:K] == DATA_SLOTS \
        == config["geometry"]["data_slots"]
    slots = {ps: a.index(victim) for ps, a in healthy.items()}
    assert {str(ps): s for ps, s in slots.items()} \
        == stated["slot_lost_by_pg"]
    assert victim not in {a[0] for a in healthy.values()}
    osdmap.mark_down(victim)
    osdmap.mark_out(victim)
    out = acting_of(osdmap, 8)
    assert all(recovered_pool.holes(a, 17) == [] for a in out.values())
    assert {str(ps): recovered_pool.repointed(healthy[ps], out[ps], victim)
            for ps in range(8)} == stated["repointed_by_pg"]
    primaries = {}
    for ps, a in out.items():
        primaries.setdefault(str(a[0]), []).append(ps)
    assert primaries == stated["primaries"]
    assert {str(ps): next(r["new"] for r in stated["repointed_by_pg"][
        str(ps)] if r["lost"]) for ps in range(8)} \
        == stated["backfill_targets_by_pg"]
    helpers = stated["repair_programs"]["helpers_by_lost_slot"]
    for slot in sorted(set(slots.values())):
        survivors = [s for s in range(N) if s != slot]
        assert sorted(coder.minimum_to_decode([slot], survivors)) \
            == helpers[str(slot)]
