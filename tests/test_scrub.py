"""Shallow scrub + cluster scrub scheduling (ref: src/osd/scrubber/ —
shallow pass compares metadata only; osd_scrub_sched.cc schedules
shallow every min_interval, deep every deep_scrub_interval; only
active+clean PGs scrub)."""

import numpy as np
import pytest

from ceph_tpu.osd.ecbackend import ECBackend, HINFO_KEY, ShardSet, shard_cid
from ceph_tpu.osd.memstore import Transaction
from ceph_tpu.osd.pgbackend import ReplicatedBackend
from cluster_helpers import corpus, make_cluster


def ec_be(k=4, m=2, chunk=256):
    cluster = ShardSet()
    be = ECBackend(f"plugin=tpu_rs k={k} m={m}", "1.0",
                   list(range(k + m)), cluster, chunk_size=chunk)
    return be, cluster


class TestShallowScrub:
    def test_clean_pg_ec_and_replicated(self):
        for be, _ in (ec_be(), (ReplicatedBackend(
                3, "1.0", [0, 1, 2]), None)):
            be.write_objects(corpus(6, 500, seed=1))
            rep = be.shallow_scrub()
            assert rep["errors"] == []
            assert rep["checked"] > 0

    def test_detects_missing_shard_object(self):
        be, cluster = ec_be()
        be.write_objects(corpus(4, 500, seed=2))
        st = cluster.osd(be.acting[3])
        st.queue_transaction(
            Transaction().remove(shard_cid(be.pg, 3), "obj-1"))
        errs = be.shallow_scrub()["errors"]
        assert ("obj-1", 3, "missing") in errs

    def test_detects_size_mismatch_without_reading_data(self):
        be, cluster = ec_be()
        be.write_objects(corpus(4, 500, seed=3))
        st = cluster.osd(be.acting[2])
        st.queue_transaction(
            Transaction().truncate(shard_cid(be.pg, 2), "obj-0", 7))
        errs = be.shallow_scrub()["errors"]
        assert any(n == "obj-0" and s == 2 and "size" in what
                   for n, s, what in errs)

    def test_detects_lost_hinfo_attr_and_stray(self):
        be, cluster = ec_be()
        be.write_objects(corpus(3, 400, seed=4))
        st = cluster.osd(be.acting[1])
        cid = shard_cid(be.pg, 1)
        st.queue_transaction(Transaction().rmattr(cid, "obj-2", HINFO_KEY))
        st.queue_transaction(Transaction().write(cid, "ghost", 0, b"boo"))
        errs = be.shallow_scrub()["errors"]
        assert ("obj-2", 1, "no hinfo attr") in errs
        assert ("ghost", 1, "stray object") in errs

    def test_behind_shard_is_not_flagged(self):
        be, _ = ec_be()
        be.write_objects(corpus(3, 300, seed=5))
        dead = be.acting[0]
        be.write_objects(corpus(3, 300, seed=6, prefix="new"),
                         dead_osds={dead})
        # slot 0 misses the new objects — that's lag, not corruption
        errs = be.shallow_scrub()["errors"]
        assert errs == []

    def test_corruption_invisible_to_shallow_visible_to_deep(self):
        be, cluster = ec_be()
        be.write_objects(corpus(3, 400, seed=7))
        st = cluster.osd(be.acting[0])
        obj = st.collections[shard_cid(be.pg, 0)]["obj-0"]
        obj.data[3] ^= 1  # same size, same attrs -> shallow-clean
        assert be.shallow_scrub()["errors"] == []
        assert ("obj-0", 0) in be.deep_scrub()["inconsistent"]


class TestScrubScheduling:
    def test_periodic_shallow_then_deep(self):
        c = make_cluster(pg_num=4)
        c.write(corpus(12, 400, seed=8))
        c.scrub_interval = 50.0
        c.deep_scrub_interval = 500.0
        c.tick(60)  # past shallow interval
        assert c.perf.get("scrubs_shallow") >= c.pg_num
        before_deep = c.perf.get("scrubs_deep")
        for _ in range(10):
            c.tick(60)
        assert c.perf.get("scrubs_deep") >= c.pg_num > before_deep
        assert c.perf.get("scrub_errors") == 0

    def test_scrub_finds_injected_bit_rot(self):
        c = make_cluster(pg_num=2)
        objs = corpus(6, 300, seed=9)
        c.write(objs)
        c.scrub_interval = 10.0
        c.deep_scrub_interval = 30.0
        name = next(iter(objs))
        ps = c.locate(name)
        be = c.pgs[ps]
        st = c.cluster.osd(be.acting[1])
        st.collections[shard_cid(be.pg, 1)][name].data[0] ^= 0xFF
        for _ in range(10):
            c.tick(12)
            if c.perf.get("scrub_errors"):
                break
        assert c.perf.get("scrub_errors") >= 1
        assert ps in c.scrub_reports

    def test_degraded_pg_not_scrubbed(self):
        c = make_cluster(pg_num=4, down_out_interval=10_000)
        c.write(corpus(8, 300, seed=10))
        c.scrub_interval = 10.0
        victim = c.pgs[0].acting[0]
        c.kill_osd(victim)
        c.tick(40)  # victim marked down; its PGs degraded
        degraded = {ps for ps in range(c.pg_num)
                    if victim in c.pgs[ps].acting}
        healthy = set(range(c.pg_num)) - degraded
        assert degraded, "victim should host at least one PG"
        # only healthy PGs scrubbed
        scrubbed = set(c.last_scrub)
        assert degraded.isdisjoint(scrubbed)
        if healthy:
            assert healthy & scrubbed
