"""A Clay pool (k=8 m=4 d=11, the MSR regenerating code) served end to
end: its writes take one fused device launch (the static encode over the
sub-chunk layout, then the crcs of all 12 rows), and when an OSD is out
for good each PG rebuilds the lost row from a quarter row of each of its
11 helpers, which the sources read, verify and slice.

Wire tier at a small size: 64 KiB objects (Clay's smallest stripe: rows
of 8 KiB, 64 sub-chunks of 128 bytes), 13 OSDs (one to spare), 2 PGs,
cephx, TinStore, seeded. One writer runs while a non-primary OSD is
stopped, marked down and out. Every object's 12 rows on the *new*
acting set are held to the benchmark's plain reference
(`bench/reference/clay_codeword.py`); the sources shipped exactly
11 x row / 4 bytes for each rebuilt object. The CRUSH case is the
benchmark's map: 13 OSDs, 8 PGs, 100 tries, osd.3 out."""

import json
import os
import threading
import time

import numpy as np
import pytest

from bench.reference import clay_codeword, recovered_pool
from ceph_tpu.chaos import load_factor
from ceph_tpu.crush.map import Tunables, build_hierarchy, ec_rule
from ceph_tpu.osd.ecbackend import ECBackend, shard_cid
from ceph_tpu.osd.osdmap import OSDMap, PGPool
from ceph_tpu.osd.standalone import StandaloneCluster
from ceph_tpu.utils.tracing import span_log

_LF = load_factor()
K, M, D = 8, 4, 11
N = K + M
UNIT = 8192
SIZE = 64 << 10
ROW = SIZE // K
PROFILE = f"plugin=clay k={K} m={M} d={D}"
BACKLOG = 24
SERVE = ("recovery.serve_ranges", "recovery.serve_ranges.read",
         "recovery.serve_ranges.verify", "recovery.serve_ranges.slice")
COUNTERS = ("recovered_objects", "recover_wire_bytes",
            "recover_range_bytes_served", "recover_range_frames_served",
            "recover_range_verify_bytes", "recover_host_launches",
            "host_decode_launches", "recover_launches")
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


def build_write_program():
    """The fused Clay write for 64 KiB objects, built before any client
    op (the dense lowering takes seconds to compile)."""
    be = ECBackend(PROFILE, "0.0", list(range(N)), chunk_size=UNIT)
    be.write_objects({"warm": np.zeros(SIZE, np.uint8)})
    return be


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
    where a daemon suspects a live peer (a probe of the boot's peering
    waits 1 s, and a loaded host misses it), since such a primary writes
    round that peer's shard until the next map, as the benchmark's
    drivers do (`bench/drivers/rados.py` `warm`)."""
    for _ in range(tries):
        c = StandaloneCluster(n_osds=N + 1, pg_num=2, profile=PROFILE,
                              chunk_size=UNIT, down_out_interval=600.0,
                              hb_interval=0.5, hb_grace=30.0, store="tin",
                              store_dir=str(tmp_path_factory.mktemp("tin")),
                              cephx=True, secret=b"clay pool secret" * 2)
        try:
            c.wait_for_clean(timeout=60 * _LF)
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
    non-primary OSD stopped, marked down and out, the pool rebuilt to
    clean. No profiler session is live."""
    rng = np.random.default_rng(3811)
    payloads = [rng.integers(0, 256, SIZE, np.uint8).tobytes()
                for _ in range(8)]
    build_write_program()
    c, cl, written = whole_pool(tmp_path_factory, payloads)
    try:
        old = acting_of(mon_map(c), c.pg_num)
        primaries = {a[0] for a in old.values()}
        victim = max((o for o in c.osd_ids() if o not in primaries),
                     key=lambda o: (sum(o in a[:K] for a in old.values()),
                                    -o))
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
        t_fail = time.perf_counter()
        before = {key: counter(c, key) for key in COUNTERS}
        c.kill_osd(victim)
        cl.osd_down(victim)
        c._wait(lambda: all(not d.osdmap.osd_up[victim] for d in live(c)),
                15 * _LF, f"every daemon's map shows osd.{victim} down")
        cl.osd_out(victim)
        c._wait(lambda: all(d.osdmap.osd_weight[victim] == 0
                            for d in live(c)),
                15 * _LF, f"every daemon's map shows osd.{victim} out")
        c.wait_for_clean(timeout=120 * _LF)
        stop.set()
        writer.join(30)
        c.wait_for_clean(timeout=30 * _LF)
        yield {"c": c, "cl": cl, "payloads": payloads, "written": written,
               "old": old, "new": acting_of(mon_map(c), c.pg_num),
               "victim": victim, "t_fail": t_fail, "failed": failed,
               "rise": {key: counter(c, key) - was
                        for key, was in before.items()}}
    finally:
        c.shutdown()


def test_every_object_is_a_clay_codeword_on_the_new_acting_set(recovered):
    c, cl = recovered["c"], recovered["cl"]
    assert recovered["failed"] == []
    assert len(recovered["written"]) > BACKLOG       # the writer wrote
    wrong = []
    for name, pay in recovered["written"].items():
        ps = cl.osdmap.object_to_pg(1, name)[1]
        rows, crcs = stored(c, recovered["new"][ps], ps, name)
        got = clay_codeword.check(recovered["payloads"][pay], rows, K, M, D,
                                  UNIT)
        if got != {"data_wrong": [], "planes_wrong": 0} \
                or list(crcs) != list(clay_codeword.crcs(np.stack(rows))):
            wrong.append((name, ps, got))
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
    assert any(r["lost"] and r["slot"] < K for r in moved)


def test_every_rebuilt_object_pulled_a_quarter_row_of_eleven_helpers(
        recovered):
    rise = recovered["rise"]
    rebuilt = rise["recovered_objects"]
    lost_pgs = {ps for ps, a in recovered["old"].items()
                if recovered["victim"] in a}
    cl = recovered["cl"]
    assert rebuilt >= sum(1 for i in range(BACKLOG) if cl.osdmap.object_to_pg(
        1, f"backlog-{i}")[1] in lost_pgs) >= 1
    assert rise["recover_range_bytes_served"] == rebuilt * D * (ROW // 4)
    assert rise["recover_wire_bytes"] == rise["recover_range_bytes_served"]
    # every source checks the full rows it slices
    assert rise["recover_range_verify_bytes"] == rebuilt * D * ROW
    assert rise["recover_range_frames_served"] >= D
    assert rise["recover_launches"] >= 1
    assert rise["recover_host_launches"] == 0
    assert rise["host_decode_launches"] == 0


def test_the_writes_took_the_fused_vector_program(recovered):
    c = recovered["c"]
    assert counter(c, "fused_write_launches") >= len(recovered["written"])
    assert counter(c, "encode_launches") == 0
    assert counter(c, "host_encode_launches") == 0


def test_the_clients_read_back_what_they_wrote(recovered):
    cl, payloads = recovered["cl"], recovered["payloads"]
    names = sorted(recovered["written"])
    rng = np.random.default_rng(3812)
    for name in rng.choice(names, 8, replace=False):
        assert bytes(cl.read(str(name))) \
            == payloads[recovered["written"][str(name)]]


def test_the_sources_log_no_span_without_a_session(recovered):
    names = {r["name"] for r in span_log(since=recovered["t_fail"])}
    assert not names & set(SERVE)


# -- the fused write of a vector code -------------------------------------

def test_the_fused_clay_write_is_the_two_launch_paths_bytes(device_path):
    be = ECBackend(PROFILE, "0.0", list(range(N)), chunk_size=UNIT)
    batch = 1
    data = np.random.default_rng(3813).integers(0, 256, (batch, K, ROW),
                                                np.uint8)
    shards, crcs = be._encode_shards_with_crcs(data, ROW)
    assert be.perf.get("fused_write_launches") == 1
    assert be.perf.get("encode_launches") == 0
    parity = np.asarray(be.coder.encode_chunks(data))
    want = np.concatenate([data, parity], axis=1)
    assert np.array_equal(shards, want)
    assert np.array_equal(crcs, be._batched_hinfo_crcs(
        want.reshape(-1, ROW)).reshape(batch, N))


def test_a_clay_without_a_device_program_takes_the_codecs_own_path(
        device_path):
    be = ECBackend(PROFILE + " impl=ref", "0.0", list(range(N)),
                   chunk_size=UNIT)
    assert be.coder.vector_encode_matrix() is None
    data = np.random.default_rng(3815).integers(0, 256, (1, K, ROW),
                                                np.uint8)
    shards, _ = be._encode_shards_with_crcs(data, ROW)
    assert be.perf.get("fused_write_launches") == 0
    assert be.perf.get("encode_launches") == 1
    assert clay_codeword.parity_failures(shards[0], K, M, D) == 0


def test_the_rs_write_program_keeps_its_cache_key(device_path):
    """An RS pool's fused write is the one program it always was: the
    same process-wide cache, the same key."""
    be = ECBackend("plugin=jerasure technique=reed_sol_van k=8 m=3", "0.0",
                   list(range(11)), chunk_size=256)
    fn = be._fused_write_program(4096, 2)
    mat = np.ascontiguousarray(be.coder.matrix, np.uint8)
    assert fn is ECBackend._fused_write_fn(mat.tobytes(), 3, 8, 4096, 2)
    assert be.perf.get("fused_write_launches") == 1


# -- CRUSH at the benchmark's map -----------------------------------------

def test_osd_3_out_is_the_configurations_map():
    crush = build_hierarchy(13, osds_per_host=1, hosts_per_rack=13)
    crush.tunables = Tunables(choose_total_tries=100)
    ec_rule(crush, 1, choose_type=1)
    osdmap = OSDMap(crush)
    osdmap.add_pool(PGPool(1, pg_num=8, size=N, min_size=K, crush_rule=1,
                           is_erasure=True))
    healthy = acting_of(osdmap, 8)
    with open(os.path.join(ROOT, "bench", "configs",
                           "rados_clay_k8m4d11_13osd_1out.json")) as f:
        stated = json.load(f)["failure"]
    victim = stated["victim"]
    assert {ps: a.index(victim) for ps, a in healthy.items()} \
        == {0: 5, 1: 1, 2: 5, 3: 1, 4: 3, 5: 7, 6: 6, 7: 6}
    osdmap.mark_down(victim)
    osdmap.mark_out(victim)
    out = acting_of(osdmap, 8)
    assert all(recovered_pool.holes(a, 13) == [] for a in out.values())
    assert {str(ps): recovered_pool.repointed(healthy[ps], out[ps], victim)
            for ps in range(8)} == stated["repointed_by_pg"]
    primaries = {}
    for ps, a in out.items():
        primaries.setdefault(str(a[0]), []).append(ps)
    assert primaries == stated["primaries"]
    assert {str(ps): next(r["new"] for r in stated["repointed_by_pg"][
        str(ps)] if r["lost"]) for ps in range(8)} \
        == {pg: t for pg, t in stated["backfill_targets_by_pg"].items()}
