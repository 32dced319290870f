"""An EC pool that lost an OSD for good (ref: OSDMonitor::tick marking a
down OSD out when `mon_osd_down_out_interval` expires): CRUSH re-points
the OSD's slots to the spare, the primaries rebuild the lost rows onto
the new members with the fused recover program, under mClock's
background_recovery class, while clients write on.

Wire tier at a small size: k=4 m=2, 64 KiB objects, 7 OSDs (one to
spare), cephx, TinStore, seeded. Every row and hinfo crc on the *new*
acting set is held to the benchmark's plain reference
(`bench/reference/recovered_pool.py`), the re-pointed rows to `rs_decode`
of k rows the failure did not touch. The round's device programs are
built before its first grant, outside the daemon and PG locks: no launch
compiles. Recovery's spans go to the span log under a live session and
nowhere without one. The CRUSH case is the benchmark's real map: 12 OSDs,
k=8 m=3, 8 PGs, osd.11 out."""

import json
import os
import threading
import time

import numpy as np
import pytest

from bench.reference import gf256, recovered_pool
from ceph_tpu.chaos import load_factor
from ceph_tpu.crush.map import (CRUSH_ITEM_NONE, EC_RULE_CHOOSE_TRIES,
                                Tunables, build_hierarchy, ec_rule)
from ceph_tpu.osd.ecbackend import shard_cid
from ceph_tpu.osd.osdmap import OSDMap, PGPool
from ceph_tpu.osd.standalone import StandaloneCluster
from ceph_tpu.utils import tracing
from ceph_tpu.utils.tracing import span_log

_LF = load_factor()
K, M = 4, 2
N = K + M
UNIT = 256
SIZE = 64 << 10
PROFILE = f"plugin=jerasure technique=reed_sol_van k={K} m={M}"
BACKLOG = 40
RECOVER_SPANS = ("recovery.pull", "recovery.stage", "recovery.launch",
                 "recovery.fetch", "recovery.push", "recovery.settle")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make(**kw):
    kw.setdefault("hb_interval", 0.5)
    kw.setdefault("hb_grace", 30.0)
    c = StandaloneCluster(n_osds=N + 1, pg_num=4, profile=PROFILE,
                          op_timeout=3.0, chunk_size=UNIT,
                          down_out_interval=600.0, **kw)
    c.wait_for_clean(timeout=30 * _LF)
    return c


def mon_map(c):
    return max((m.osdmap for m in c.mons if m.osdmap is not None),
               key=lambda m: m.epoch)


def live(c):
    return [d for d in c.osds.values() if not d._stop.is_set()]


def counter(c, key):
    return sum(int(d.ec_perf.get(key)) for d in live(c))


def acting_of(osdmap, pg_num):
    return {ps: [int(o) for o in osdmap.pg_to_up_acting_osds(1, ps)[2]]
            for ps in range(pg_num)}


def non_primary(c, acting):
    """The OSD that is no PG's primary and holds a data slot in the
    most PGs (the benchmark's victim rule)."""
    primaries = {a[0] for a in acting.values()}
    return max((o for o in c.osd_ids() if o not in primaries),
               key=lambda o: (sum(o in a[:K] for a in acting.values()), -o))


def fail_for_good(c, cl, victim):
    c.kill_osd(victim)
    cl.osd_down(victim)
    c._wait(lambda: all(not d.osdmap.osd_up[victim] for d in live(c)),
            15 * _LF, f"every daemon's map shows osd.{victim} down")
    cl.osd_out(victim)
    # a daemon still on the epoch before is clean by its own map
    c._wait(lambda: all(d.osdmap.osd_weight[victim] == 0 for d in live(c)),
            15 * _LF, f"every daemon's map shows osd.{victim} out")


def stored(c, acting, ps, name):
    """The k+m rows and hinfo crcs of one object on `acting`'s stores."""
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


@pytest.fixture(scope="module")
def recovered(device_path, tmp_path_factory):
    """One pool: a backlog written healthy, writer loops started, then a
    non-primary OSD stopped, marked down and out; the pool rebuilt to
    clean under the writers. No profiler session is live."""
    from ceph_tpu.osd.ecbackend import RecoveryRunner
    rng = np.random.default_rng(3301)
    payloads = [rng.integers(0, 256, SIZE, np.uint8).tobytes()
                for _ in range(8)]
    c = make(store="tin", store_dir=str(tmp_path_factory.mktemp("tin")),
             cephx=True, secret=b"recovering pool " * 2)
    grew, launches = [], []
    real_launch = RecoveryRunner._launch

    def watched(self, sl, pairs, bucket):
        program = self._program(pairs[0][0])
        before = program._cache_size()
        real_launch(self, sl, pairs, bucket)
        grew.append(program._cache_size() - before)
        launches.append(len(pairs))
    try:
        cl = c.client()
        written = {}
        for i in range(BACKLOG):
            cl.write({f"backlog-{i}": payloads[i % 8]})
            written[f"backlog-{i}"] = i % 8
        c.wait_for_clean(timeout=30 * _LF)
        old = acting_of(mon_map(c), c.pg_num)
        victim = non_primary(c, old)
        stop, lock, failed = threading.Event(), threading.Lock(), []

        def loop(i):
            n = 0
            while not stop.is_set():
                name, pay = f"writer-{i}-{n}", (i + n) % 8
                try:
                    cl.write({name: payloads[pay]})
                    with lock:
                        written[name] = pay
                except Exception as e:   # noqa: BLE001 — counted
                    failed.append((name, repr(e)))
                n += 1
        threads = [threading.Thread(target=loop, args=(i,), daemon=True)
                   for i in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        t_fail = time.perf_counter()
        before = {key: counter(c, key) for key in (
            "recover_launches", "recover_host_launches",
            "recovered_objects", "host_decode_launches")}
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(RecoveryRunner, "_launch", watched)
            fail_for_good(c, cl, victim)
            c.wait_for_clean(timeout=90 * _LF)
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(20)
        c.wait_for_clean(timeout=30 * _LF)
        yield {"c": c, "cl": cl, "payloads": payloads, "written": written,
               "old": old, "new": acting_of(mon_map(c), c.pg_num),
               "victim": victim, "t_fail": t_fail, "before": before,
               "failed": failed, "grew": grew, "launches": launches}
    finally:
        c.shutdown()


def test_every_pg_is_whole_on_live_osds_and_the_victim_is_out(recovered):
    c, victim = recovered["c"], recovered["victim"]
    osdmap = mon_map(c)
    for ps, acting in recovered["new"].items():
        assert recovered_pool.holes(acting, N + 1) == [], (ps, acting)
        assert victim not in acting and len(set(acting)) == N
    assert not osdmap.osd_up[victim] and osdmap.osd_weight[victim] == 0
    assert [o for o in c.osd_ids() if not osdmap.osd_up[o]] == [victim]
    moved = {ps: recovered_pool.repointed(recovered["old"][ps], acting,
                                          victim)
             for ps, acting in recovered["new"].items()}
    # the victim held a data slot somewhere, and that slot was rebuilt
    assert any(r["lost"] and r["slot"] < K
               for moves in moved.values() for r in moves)


def test_every_row_and_crc_on_the_new_acting_set_is_the_references(
        recovered):
    c, cl = recovered["c"], recovered["cl"]
    want = recovered_pool.stripes(recovered["payloads"], K, M, UNIT)
    assert len(recovered["written"]) > BACKLOG       # the writers wrote
    wrong = []
    for name, pay in recovered["written"].items():
        ps = cl.osdmap.object_to_pg(1, name)[1]
        rows, crcs = stored(c, recovered["new"][ps], ps, name)
        for s in range(N):
            if rows[s] is None or not np.array_equal(rows[s], want[pay][0][s]) \
                    or int(crcs[s]) != int(want[pay][1][s]):
                wrong.append((name, ps, s))
    assert wrong == []


def test_the_repointed_rows_are_rs_decode_of_rows_the_failure_left(
        recovered):
    c, cl = recovered["c"], recovered["cl"]
    matrix = gf256.reed_sol_van(K, M)
    rng = np.random.default_rng(3302)
    checked = 0
    for i in range(BACKLOG):
        name = f"backlog-{i}"
        ps = cl.osdmap.object_to_pg(1, name)[1]
        moves = recovered_pool.repointed(recovered["old"][ps],
                                         recovered["new"][ps],
                                         recovered["victim"])
        if not moves:
            continue
        slots = [r["slot"] for r in moves]
        rows, _ = stored(c, recovered["new"][ps], ps, name)
        assert all(row is not None for row in rows), (name, ps)
        untouched = [s for s in range(N) if s not in slots]
        assert len(untouched) >= K, (ps, moves)
        there = sorted(int(s) for s in rng.choice(untouched, K,
                                                  replace=False))
        plain = recovered_pool.rebuilt_rows(matrix, rows, there, slots)
        for j, s in enumerate(slots):
            assert np.array_equal(plain[j], rows[s]), (name, s)
            checked += 1
    assert checked >= 1


def test_the_pool_was_rebuilt_by_the_fused_device_path(recovered):
    c, before = recovered["c"], recovered["before"]
    rise = {key: counter(c, key) - was for key, was in before.items()}
    cl = recovered["cl"]
    lost_pgs = {ps for ps, acting in recovered["old"].items()
                if recovered["victim"] in acting}
    backlog_lost = sum(1 for i in range(BACKLOG)
                       if cl.osdmap.object_to_pg(1, f"backlog-{i}")[1]
                       in lost_pgs)
    assert rise["recover_launches"] >= 1
    assert rise["recover_host_launches"] == 0
    assert rise["host_decode_launches"] == 0
    assert rise["recovered_objects"] >= backlog_lost >= 1
    assert recovered["failed"] == []


def test_the_clients_read_back_what_they_wrote(recovered):
    cl, payloads = recovered["cl"], recovered["payloads"]
    names = sorted(recovered["written"])
    rng = np.random.default_rng(3303)
    for name in rng.choice(names, 12, replace=False):
        assert bytes(cl.read(str(name))) \
            == payloads[recovered["written"][str(name)]]


def test_the_pool_ends_clean(recovered):
    c = recovered["c"]
    for ps, acting in recovered["new"].items():
        primary = c.osds[acting[0]]
        assert [int(o) for o in primary.backends[ps].acting] == acting
        assert ps not in primary._recovering
    assert [(d.osd_id, p) for d in live(c) for p in d.suspect
            if p != recovered["victim"]] == []


def test_no_launch_of_a_round_compiles(recovered):
    """A PG's recover program is built when the PG is planned
    (`OSDDaemon._backfill_build`), before it asks for its reservation:
    inside `_launch`, which runs under the daemon lock and the PG's
    lock, jax's cache of the jitted program never grows."""
    assert len(recovered["grew"]) >= 1
    assert set(recovered["grew"]) == {0}


def test_recovery_logs_no_span_without_a_session(recovered):
    names = {r["name"] for r in span_log(since=recovered["t_fail"])}
    assert not names & (set(RECOVER_SPANS) | {"recovery.grant"})


def test_the_pools_map_retries_as_upstreams_ec_rule(recovered):
    from ceph_tpu.osd.cluster import SimCluster
    crush = mon_map(recovered["c"]).crush
    assert crush.tunables.choose_total_tries == EC_RULE_CHOOSE_TRIES == 100
    sim = SimCluster(n_osds=7, profile=PROFILE, pg_num=4)
    assert sim.osdmap.crush.tunables.choose_total_tries == 100
    assert sim.osdmap._om.tries == sim.osdmap._vm.tries == 100
    flat = SimCluster(n_osds=4, profile="replicated size=3", pg_num=4)
    assert flat.osdmap.crush.tunables.choose_total_tries == 51


def test_recovery_spans_reach_the_log_under_a_live_session(device_path,
                                                           tmp_path):
    """`recovery.grant` with its children and `recovery.reserve.wait`,
    each with its self time; the launch carries the helper bytes it
    decodes."""
    c = make()
    try:
        cl = c.client()
        rng = np.random.default_rng(3304)
        cl.write({f"traced-{i}": rng.integers(0, 256, SIZE,
                                              np.uint8).tobytes()
                  for i in range(12)})
        c.wait_for_clean(timeout=30 * _LF)
        victim = non_primary(c, acting_of(mon_map(c), c.pg_num))
        assert tracing.start_trace(str(tmp_path / "capture"))
        try:
            t0 = time.perf_counter()
            before = counter(c, "recovered_objects")
            fail_for_good(c, cl, victim)
            c.wait_for_clean(timeout=90 * _LF)
        finally:
            table = tracing.stop_trace()
        rebuilt = counter(c, "recovered_objects") - before
        assert rebuilt >= 1
        found = {}
        for r in span_log(since=t0):
            found.setdefault(r["name"], []).append(r)
        for name in RECOVER_SPANS + ("recovery.grant", "recovery.reserve.wait"):
            assert name in found, name
            assert name in table["stages"]
            assert all(0 <= r["self"] <= r["dur"] + 1e-9
                       for r in found[name])
        # a grant's own time is what its batch's stages leave of it
        grants = found["recovery.grant"]
        assert sum(r["self"] for r in grants) < sum(r["dur"] for r in grants)
        # every launch carries k helper rows an object
        staged = sum(r["nbytes"] for r in found["recovery.launch"])
        assert staged == rebuilt * K * (SIZE // K)
    finally:
        c.shutdown()


def test_a_moved_shard_travels_a_frame_of_rows_at_a_time():
    """`_move_shard` (a slot CRUSH re-points between two live OSDs) pulls
    a shard's rows and hinfo attrs a `readv` frame at a time, not three
    calls an object; a frame that cannot answer whole (a row the old
    holder lacks) is read row by row, and what is there moves."""
    from ceph_tpu.osd.memstore import Transaction
    from ceph_tpu.osd.standalone import RemoteStore
    c = make()
    try:
        cl = c.client()
        rng = np.random.default_rng(3305)
        sizes = {f"moved-{i}": SIZE if i % 2 else SIZE // 2
                 for i in range(24)}
        cl.write({name: rng.integers(0, 256, n, np.uint8).tobytes()
                  for name, n in sizes.items()})
        c.wait_for_clean(timeout=30 * _LF)
        acting = acting_of(mon_map(c), c.pg_num)
        ps = max(acting, key=lambda p: sum(
            cl.osdmap.object_to_pg(1, n)[1] == p for n in sizes))
        primary = c.osds[acting[ps][0]]
        be = primary.backends[ps]
        names = be.list_pg_objects()
        slot, old = N - 1, acting[ps][N - 1]
        new = next(o for o in c.osd_ids() if o not in acting[ps])
        cid = shard_cid(f"1.{ps}", slot)
        src, dst = c.osds[old].store, c.osds[new].store
        gone = next(n for n in names if sizes[n] == SIZE)
        src.queue_transaction(Transaction().remove(cid, gone))
        whole = [n for n in names if sizes[n] != SIZE]
        assert len(names) >= 4 and whole
        reads = []
        real_read = RemoteStore.read
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(RemoteStore, "read", lambda self, cid_, oid, *a:
                          (reads.append(oid), real_read(self, cid_, oid,
                                                        *a))[1])
            before = primary.perf.get("move_objects")
            primary._move_shard(be, slot, old, new)
        try:
            assert be.acting[slot] == new
            assert primary.perf.get("move_objects") - before \
                == len(names) - 1
            for name in names:
                if name == gone:
                    assert not dst.exists(cid, name)
                    continue
                assert np.array_equal(dst.read(cid, name),
                                      src.read(cid, name)), name
                assert dst.getattr(cid, name, "hinfo_key") \
                    == src.getattr(cid, name, "hinfo_key")
            # the frame of the other length answered whole: none of its
            # rows was read alone
            assert not set(reads) & set(whole)
            assert set(reads) == {n for n in names
                                  if sizes[n] == SIZE and n != gone}
        finally:
            be.acting[slot] = old
    finally:
        c.shutdown()


# -- CRUSH at the benchmark's map -----------------------------------------

def real_map(tries):
    crush = build_hierarchy(12, osds_per_host=1, hosts_per_rack=12)
    crush.tunables = Tunables(choose_total_tries=tries)
    ec_rule(crush, 1, choose_type=1)
    osdmap = OSDMap(crush)
    osdmap.add_pool(PGPool(1, pg_num=8, size=11, min_size=8, crush_rule=1,
                           is_erasure=True))
    return osdmap


def config_file(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("tries", [51, EC_RULE_CHOOSE_TRIES])
def test_the_healthy_map_is_the_degraded_cells(tries):
    """More tries move no slot of a whole cluster: osd.11 sits where
    `rados_k8m3_12osd_1down.json` says, at the old 51 and at 100."""
    healthy = acting_of(real_map(tries), 8)
    stated = config_file("rados_k8m3_12osd_1down")["failure"]
    assert {str(ps): a.index(11) for ps, a in healthy.items()} \
        == stated["slot_lost_by_pg"]
    assert healthy == acting_of(real_map(100), 8)
    assert all(len(set(a)) == 11 for a in healthy.values())


@pytest.mark.parametrize("tries,holes", [(51, {1: [6]}),
                                         (EC_RULE_CHOOSE_TRIES, {})])
def test_osd_11_out_leaves_no_hole_at_the_ec_rules_tries(tries, holes):
    osdmap = real_map(tries)
    healthy = acting_of(osdmap, 8)
    osdmap.mark_down(11)
    osdmap.mark_out(11)
    out = acting_of(osdmap, 8)
    assert {ps: recovered_pool.holes(a, 12) for ps, a in out.items()
            if recovered_pool.holes(a, 12)} == holes
    # the vectorized mapper and the oracle agree, slot for slot
    up = np.asarray(osdmap.pgs_to_up(1))
    assert [[int(o) for o in row] for row in up] \
        == [out[ps] for ps in range(8)]
    assert (up == CRUSH_ITEM_NONE).sum() == sum(map(len, holes.values()))
    if not holes:
        stated = config_file("rados_k8m3_12osd_1out")["failure"]
        assert {str(ps): recovered_pool.repointed(healthy[ps], out[ps], 11)
                for ps in range(8)} == stated["repointed_by_pg"]
        assert stated["victim"] == 11 and stated["pgs_with_a_hole"] == 0
