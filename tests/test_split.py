"""PG split (pg_num increase) tests — the autoscaler's executor
(refs: src/osd/PG.cc split machinery, ceph_stable_mod re-bucketing;
src/mon/OSDMonitor.cc pg_num handling; src/pybind/mgr/pg_autoscaler
`on` mode). Every byte must survive, children must land on their own
CRUSH targets via pg_temp-protected backfill, and a degraded or
quorum-less cluster must refuse to split."""

import numpy as np
import pytest

from ceph_tpu.client.objecter import Objecter
from ceph_tpu.osd.cluster import SimCluster


def make(n_osds=12, pg_num=4, **kw):
    kw.setdefault("profile", "plugin=tpu_rs k=4 m=2")
    c = SimCluster(n_osds=n_osds, pg_num=pg_num, **kw)
    return c, Objecter(c)


def write_corpus(ob, n=60, seed=1, size_lo=50, size_hi=900):
    rng = np.random.default_rng(seed)
    objs = {f"split-{seed}-{i}":
            rng.integers(0, 256, int(rng.integers(size_lo, size_hi)),
                         np.uint8).tobytes() for i in range(n)}
    ob.write(objs)
    return objs


def settle(c, rounds=150):
    for _ in range(rounds):
        if not c.backfills:
            return
        c.tick(6.0)
    raise AssertionError("backfills never drained")


class TestSplit:
    def test_double_preserves_every_byte_and_rebalances(self):
        c, ob = make(pg_num=4)
        objs = write_corpus(ob, n=80)
        before_epoch = c.osdmap.epoch
        rep = c.split_pgs(8)
        assert rep["pg_num"] == 8 and c.pg_num == 8
        assert c.osdmap.epoch > before_epoch       # quorum-gated bump
        assert set(rep["children"]) == {4, 5, 6, 7}
        assert rep["children"] == {4: 0, 5: 1, 6: 2, 7: 3}
        # stable_mod: a healthy split re-homes roughly half the data
        assert 0 < rep["objects_moved"] < len(objs)
        # reads correct IMMEDIATELY (children still on parent OSDs,
        # pg_temp protects the transition)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
        # objects live in the PG locate() says, parents kept the rest
        for name in objs:
            ps = c.locate(name)
            assert name in c.pgs[ps].object_sizes
        sizes = [len(c.pgs[ps].object_sizes) for ps in range(8)]
        assert sum(sizes) == len(objs)
        settle(c)
        # children ended on their own CRUSH targets, pg_temp cleared
        for ps in range(8):
            assert c.pgs[ps].acting == c._up(ps), ps
            assert (1, ps) not in c.osdmap.pg_temp
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
        # scrub-clean across the board
        for ps in range(8):
            rep = c.pgs[ps].deep_scrub(dead_osds=c._dead_osds())
            assert rep["inconsistent"] == [], ps

    def test_non_power_of_two_target(self):
        c, ob = make(pg_num=4)
        objs = write_corpus(ob, n=40, seed=2)
        c.split_pgs(6)                 # children 4, 5 from parents 0, 1
        assert c.pg_num == 6
        settle(c)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
        assert sum(len(c.pgs[ps].object_sizes)
                   for ps in range(6)) == len(objs)

    def test_writes_during_child_backfill_survive(self):
        c, ob = make(pg_num=4)
        first = write_corpus(ob, n=40, seed=3)
        c.split_pgs(8)
        # backfills of children are in flight NOW; write through them
        assert c.backfills
        second = write_corpus(ob, n=40, seed=4)
        settle(c)
        for name, want in {**first, **second}.items():
            assert ob.read(name).tobytes() == want

    def test_split_then_kill_revive_delta_replay_still_exact(self):
        c, ob = make(pg_num=4, down_out_interval=600.0)
        objs = write_corpus(ob, n=40, seed=5)
        c.split_pgs(8)
        settle(c)
        victim = c.pgs[5].acting[0]
        c.kill_osd(victim)
        c.tick(30.0)
        more = write_corpus(ob, n=20, seed=6)
        c.revive_osd(victim)           # PG-log delta replay incl. the
        c.tick(30.0)                   # split's create/delete entries
        for name, want in {**objs, **more}.items():
            assert ob.read(name).tobytes() == want

    def test_refuses_degraded_or_busy_or_shrink(self):
        c, ob = make(pg_num=4, down_out_interval=600.0)
        write_corpus(ob, n=20, seed=7)
        with pytest.raises(ValueError, match="merges"):
            c.split_pgs(4)
        c.kill_osd(c.pgs[0].acting[0])
        with pytest.raises(ValueError, match="degraded"):
            c.split_pgs(8)

    def test_refuses_without_quorum(self):
        c, ob = make(pg_num=4)
        write_corpus(ob, n=10, seed=8)
        c.kill_mon(0)
        c.kill_mon(1)                  # 1 of 3 left: no quorum
        with pytest.raises(ValueError, match="quorum"):
            c.split_pgs(8)
        c.revive_mon(0)
        c.split_pgs(8)                 # quorum back: split proceeds
        assert c.pg_num == 8

    def test_apply_autoscale_executes_recommendation(self):
        # 12 in-OSDs x 100 / size 6 = 200 -> pow2 256; cap it to keep
        # the test fast and prove max_pg_num works
        c, ob = make(pg_num=4)
        objs = write_corpus(ob, n=30, seed=9)
        rep = c.apply_autoscale(max_pg_num=16)
        assert rep is not None and c.pg_num == 16
        settle(c)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
        # already at the cap: a second run is a no-op
        assert c.apply_autoscale(max_pg_num=16) is None

    def test_split_on_persistent_store(self, tmp_path):
        c, ob = make(pg_num=4, store="tin",
                     store_dir=str(tmp_path / "osds"))
        objs = write_corpus(ob, n=30, seed=10)
        c.split_pgs(8)
        settle(c)
        # the split survives SIGKILL of every OSD: WAL replay rebuilds
        # parent AND child collections
        for o in list(c.cluster.stores):
            c.cluster.stores[o].crash()
            c.cluster.stores[o].remount()
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want

    @pytest.mark.slow   # ~27 s; EC-pool split stays tier-1 (r10)
    def test_replicated_pool_splits_too(self):
        c, ob = make(pg_num=4, profile="replicated size=3", n_osds=9)
        objs = write_corpus(ob, n=40, seed=11)
        c.split_pgs(8)
        settle(c)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
