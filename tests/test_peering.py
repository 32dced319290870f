"""PeeringState tests — GetInfo/GetLog/GetMissing classification and
missing-plan computation (ref: src/osd/PeeringState.{h,cc} phases;
pg_state strings per ceph pg stat)."""

import numpy as np
import pytest

from ceph_tpu.osd.cluster import StaleMap
from ceph_tpu.osd.ecbackend import ECBackend, ShardSet
from ceph_tpu.osd.peering import (BACKFILL, interval_maybe_went_rw, peer)
from cluster_helpers import corpus, make_cluster


def make_be(k=4, m=2):
    cluster = ShardSet()
    be = ECBackend(f"plugin=tpu_rs k={k} m={m}", "1.0",
                   list(range(k + m)), cluster, chunk_size=128)
    return be


def alive(n, dead=()):
    a = np.ones(n, dtype=bool)
    for d in dead:
        a[d] = False
    return a


class TestClassification:
    def test_clean(self):
        be = make_be()
        be.write_objects(corpus(4, 256, seed=1))
        res = peer(be, alive(6))
        assert res.state == "active+clean"
        assert res.missing == {}
        assert res.auth_version == res.head == be.pg_log.head

    def test_degraded_on_dead_shard(self):
        be = make_be()
        be.write_objects(corpus(4, 256, seed=2))
        res = peer(be, alive(6, dead=[0]))
        assert res.state == "active+degraded"
        assert res.serviceable

    def test_down_below_min_size(self):
        be = make_be()  # k=4 -> min_live 4
        res = peer(be, alive(6, dead=[0, 1, 2]))
        assert res.state == "down"
        assert not res.serviceable

    def test_incomplete_when_fresh_quorum_lost(self):
        be = make_be()
        be.write_objects(corpus(2, 256, seed=3))
        # a write lands while osd.0 is down -> only shards 1..5 fresh
        be.write_objects({"late": b"x" * 100}, dead_osds={0})
        # then two FRESH shards die and osd.0 comes back: 4 live
        # (>= min) but only 3 reach the newest write
        res = peer(be, alive(6, dead=[1, 2]))
        assert res.state == "incomplete"
        assert not res.serviceable

    def test_backfilling_flag(self):
        be = make_be()
        be.write_objects(corpus(2, 256, seed=4))
        res = peer(be, alive(6), backfilling=True)
        assert res.state == "active+backfilling"


class TestMissingPlan:
    def test_replay_names(self):
        be = make_be()
        be.write_objects({"a": b"1" * 64, "b": b"2" * 64})
        be.write_objects({"c": b"3" * 64}, dead_osds={5})
        res = peer(be, alive(6))
        assert res.missing == {5: ["c"]}
        assert res.state == "active+degraded"

    def test_backfill_after_log_trim(self):
        be = make_be()
        be.pg_log.max_entries = 4
        be.write_objects({"a": b"1" * 64}, dead_osds={5})
        for i in range(6):  # trim past shard 5's cursor
            be.write_objects({f"x{i}": bytes([i]) * 64})
        res = peer(be, alive(6))
        assert res.missing[5] == BACKFILL

    def test_dead_shards_not_in_plan(self):
        be = make_be()
        be.write_objects({"a": b"1" * 64}, dead_osds={5})
        res = peer(be, alive(6, dead=[5]))
        assert 5 not in res.missing
        assert res.state == "active+degraded"


class TestClusterIntegration:
    def test_health_reports_pg_states(self):
        c = make_cluster(pg_num=4)
        c.write(corpus(8, 300, seed=5))
        h = c.health()
        assert set(h["pg_states"]) == {0, 1, 2, 3}
        assert all(s == "active+clean" for s in h["pg_states"].values())
        assert h["pgs_down"] == 0

    def test_down_pg_parks_client_ops(self):
        c = make_cluster(pg_num=4, n_osds=12, down_out_interval=10_000)
        objs = corpus(8, 300, seed=6)
        c.write(objs)
        # kill enough OSDs of pg 0 to push it below min_size
        victims = c.pgs[0].acting[:c.m + 1]
        for v in victims:
            c.kill_osd(v)
        assert c.pg_state(0) == "down"
        primary = c.osdmap.pg_to_up_acting_osds(1, 0)[3]
        with pytest.raises(StaleMap, match="parked|not answering"):
            c.client_rpc(primary, c.osdmap.epoch, "read", 0,
                         [n for n in objs if c.locate(n) == 0][:1])
        # revive -> peering makes it serviceable again
        for v in victims:
            c.revive_osd(v)
        assert c.pg_state(0).startswith("active")
        assert c.verify_all(objs) == len(objs)

    def test_revive_executes_missing_plan(self):
        c = make_cluster(pg_num=4, n_osds=12, down_out_interval=10_000)
        c.write(corpus(8, 300, seed=7))
        c.kill_osd(3)
        c.tick(30)
        late = corpus(6, 300, seed=8, prefix="late")
        c.write(late)
        # some PG on osd.3 now has a missing plan for it
        plans = [peer(c.pgs[ps], np.ones(12, dtype=bool)).missing
                 for ps in range(4)]
        assert any(plans)
        c.revive_osd(3)
        for ps in range(4):
            res = peer(c.pgs[ps], c.alive,
                       backfilling=ps in c.backfills)
            assert res.missing == {}, ps


class TestContiguousCursor:
    def test_behind_shard_never_serves_missed_overwrite(self):
        # regression: osd revives, replay deferred, NEW write arrives;
        # its cursor must stay behind so reads never pick its stale
        # chunk of the overwritten object
        be = make_be()
        objs = {"obj": b"\xaa" * 512}
        be.write_objects(objs)
        be.write_objects({"obj": b"\xbb" * 512}, dead_osds={2})  # v2 missed
        # slot 2 "revives" (no replay) and receives a new write
        be.write_objects({"other": b"\xcc" * 256})
        assert be.shard_applied[2] < be.pg_log.head
        # read of the overwritten object must not use slot 2
        got = be.read_object("obj")
        assert got.tobytes() == b"\xbb" * 512
        # and peering still plans its replay
        res = peer(be, alive(6))
        assert set(res.missing) == {2}
        assert "obj" in res.missing[2]


class TestUpThru:
    """Interval-freshness consult (ref: osd_info_t::up_thru +
    PeeringState WaitUpThru / PastIntervals maybe_went_rw)."""

    def test_wait_up_thru_holds_activation(self):
        be = make_be()
        be.write_objects(corpus(4, 256, seed=11))
        # healthy shards, but the primary's up_thru lags the interval:
        # WaitUpThru, not active — I/O must stay parked
        res = peer(be, alive(6), interval_start=9, up_thru=4)
        assert res.state == "peering"
        assert res.needs_up_thru
        assert not res.serviceable
        # the monitors commit the up_thru -> active
        res = peer(be, alive(6), interval_start=9, up_thru=9)
        assert res.state == "active+clean"
        assert not res.needs_up_thru

    def test_down_and_incomplete_outrank_wait_up_thru(self):
        # a PG below min_size is down, not "peering": WaitUpThru only
        # gates PGs that could otherwise activate
        be = make_be()
        res = peer(be, alive(6, dead=[0, 1, 2]),
                   interval_start=9, up_thru=4)
        assert res.state == "down"
        assert not res.needs_up_thru

    def test_maybe_went_rw(self):
        assert interval_maybe_went_rw(5, 5)
        assert interval_maybe_went_rw(5, 7)
        # primary never recorded up_thru at the interval's start: the
        # interval provably never served writes
        assert not interval_maybe_went_rw(5, 4)

    def test_cluster_blocks_new_interval_without_quorum(self):
        """Monitor loss visibly gates activation: a new interval's
        primary cannot record up_thru, so the PG parks client I/O
        until quorum heals (the WaitUpThru -> MOSDAlive flow)."""
        c = make_cluster(pg_num=4, n_osds=12, down_out_interval=10_000)
        objs = corpus(8, 300, seed=21)
        c.write(objs)
        assert all(c.pg_state(ps).startswith("active")
                   for ps in range(4))
        ps = 0
        old_primary = c._pg_primary[ps]
        # quorum dies, THEN a map change starts a new interval (the
        # admin/balancer path mutates the map outside the tick pump)
        c.kill_mon(0)
        c.kill_mon(1)
        c.osdmap.mark_out(old_primary)
        c._repeer_all()
        for _ in range(40):
            if c.backfills:
                c.tick(6.0)
        new_primary = c.osdmap.pg_to_up_acting_osds(1, ps)[3]
        assert new_primary != old_primary
        c.tick(6.0)   # up_thru request runs -> NoQuorum -> deferred
        assert c.pg_state(ps) == "peering"
        with pytest.raises(StaleMap, match="peering"):
            c.client_rpc(new_primary, c.osdmap.epoch, "read", ps,
                         [n for n in objs if c.locate(n) == ps][:1])
        # quorum heals -> the MOSDAlive retry commits -> active
        c.revive_mon(0)
        c.tick(6.0)
        assert c.pg_state(ps).startswith("active")
        assert int(c.osdmap.osd_up_thru[new_primary]) \
            >= c.interval_start[ps]
        assert c.verify_all(objs) == len(objs)

    def test_kill_primary_before_active_not_waited_on(self):
        """The VERDICT demand-4 case: a new interval's primary dies
        BEFORE anyone saw it active (up_thru never recorded). The
        cluster must neither wait on nor trust that interval — the
        next primary activates from the surviving shards and every
        byte serves."""
        c = make_cluster(pg_num=4, n_osds=12, down_out_interval=30.0)
        objs = corpus(10, 300, seed=22)
        c.write(objs)
        ps = 0
        old_primary = c._pg_primary[ps]
        # new interval born under quorum loss: the backfill off the
        # admin-outed primary runs mon-free, but once the cutover
        # promotes the new primary it can never record up_thru...
        c.kill_mon(0)
        c.kill_mon(1)
        c.osdmap.mark_out(old_primary)
        c._repeer_all()
        for _ in range(60):
            if not c.backfills:
                break
            c.tick(6.0)
        assert not c.backfills
        doomed_primary = c.osdmap.pg_to_up_acting_osds(1, ps)[3]
        assert doomed_primary != old_primary
        doomed_start = c.interval_start[ps]
        assert c.pg_state(ps) == "peering"
        # ...and dies pre-activation
        c.kill_osd(doomed_primary)
        assert not interval_maybe_went_rw(
            doomed_start, int(c.osdmap.osd_up_thru[doomed_primary]))
        # quorum heals; failure detection + repeer promote the NEXT
        # primary, which records ITS up_thru and goes active — the
        # dead pre-active interval blocks nothing
        c.revive_mon(0)
        c.revive_mon(1)
        c.tick(30.0)
        c.tick(40.0)
        for _ in range(120):
            if not c.backfills:
                break
            c.tick(6.0)
        final_primary = c.osdmap.pg_to_up_acting_osds(1, ps)[3]
        assert final_primary != doomed_primary
        assert c.pg_state(ps).startswith("active")
        assert int(c.osdmap.osd_up_thru[final_primary]) \
            >= c.interval_start[ps]
        # the doomed interval was never trusted: it still has no
        # up_thru claim at its start epoch
        assert not interval_maybe_went_rw(
            doomed_start, int(c.osdmap.osd_up_thru[doomed_primary]))
        assert c.verify_all(objs) == len(objs)


def test_undersized_slot_classified_not_crashed():
    # hole sentinel is CRUSH_ITEM_NONE (positive!) — peer must treat it
    # as an unfilled slot, not index the alive array with it
    from ceph_tpu.crush.map import CRUSH_ITEM_NONE
    be = make_be()
    be.write_objects(corpus(2, 256, seed=9))
    be.acting[5] = CRUSH_ITEM_NONE
    res = peer(be, alive(6))
    assert "undersized" in res.state
    assert res.serviceable  # 5 live shards >= k=4
