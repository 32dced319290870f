"""Verify-on-read / EIO-reconstruct / pg repair tests (refs:
BlueStore::_verify_csum on every read; qa/standalone/erasure-code/
test-erasure-eio.sh read-error recovery; `ceph pg repair`)."""

import numpy as np
import pytest

from ceph_tpu.osd.ecbackend import ECBackend, ShardSet, shard_cid
from ceph_tpu.osd.pgbackend import ReplicatedBackend
from cluster_helpers import corpus, make_cluster


def ec_be(k=4, m=2):
    cluster = ShardSet()
    be = ECBackend(f"plugin=tpu_rs k={k} m={m}", "1.0",
                   list(range(k + m)), cluster, chunk_size=128)
    return be, cluster


def rot(cluster, be, slot, name, flip=3):
    obj = cluster.osd(be.acting[slot]).collections[
        shard_cid(be.pg, slot)][name]
    obj.data[flip] ^= 0x5A


class TestECReadEIO:
    def test_read_survives_data_shard_rot_and_repairs(self):
        be, cluster = ec_be()
        objs = corpus(6, 500, seed=1)
        be.write_objects(objs)
        rot(cluster, be, 1, "obj-2")
        got = be.read_objects(list(objs))
        for n, d in objs.items():
            assert np.array_equal(got[n], d), n
        assert be.eio_stats["read_eio"] == 1
        assert be.eio_stats["repaired"] == 1
        # the rot is gone: scrub clean, next read takes the fast path
        assert be.deep_scrub()["inconsistent"] == []
        eio_before = be.eio_stats["read_eio"]
        be.read_objects(["obj-2"])
        assert be.eio_stats["read_eio"] == eio_before

    def test_read_survives_multiple_rotten_shards(self):
        be, cluster = ec_be()  # m=2: two rotten shards recoverable
        objs = corpus(4, 400, seed=2)
        be.write_objects(objs)
        rot(cluster, be, 0, "obj-1")
        rot(cluster, be, 3, "obj-1", flip=9)
        assert np.array_equal(be.read_object("obj-1"), objs["obj-1"])
        assert be.deep_scrub()["inconsistent"] == []

    def test_verify_off_skips_checks(self):
        be, cluster = ec_be()
        be.write_objects(corpus(2, 300, seed=3))
        rot(cluster, be, 1, "obj-0")
        got = be.read_objects(["obj-0"], verify=False)
        assert be.eio_stats["read_eio"] == 0
        # without verification the rot flows through (that's the point
        # of the flag: benches measure the raw path)
        assert got["obj-0"].shape == (300,)

    def test_repair_pg_fixes_parity_rot(self):
        be, cluster = ec_be()
        objs = corpus(5, 400, seed=4)
        be.write_objects(objs)
        rot(cluster, be, 4, "obj-3")   # parity shard: reads don't see it
        rot(cluster, be, 5, "obj-0", flip=1)
        rep = be.repair_pg()
        assert rep["repaired"] == 2 and rep["objects"] == 2
        assert be.deep_scrub()["inconsistent"] == []
        for n, d in objs.items():
            assert np.array_equal(be.read_object(n), d)


class TestReplicatedReadEIO:
    def test_failover_and_repair(self):
        be = ReplicatedBackend(3, "1.0", [0, 1, 2])
        objs = corpus(4, 300, seed=5)
        be.write_objects(objs)
        st = be.cluster.osd(be.acting[0])
        st.collections[shard_cid(be.pg, 0)]["obj-1"].data[2] ^= 0xFF
        got = be.read_object("obj-1")   # primary rotten -> failover
        assert np.array_equal(got, objs["obj-1"])
        assert be.eio_stats["read_eio"] == 1
        assert be.eio_stats["repaired"] == 1
        assert be.deep_scrub()["inconsistent"] == []

    def test_all_replicas_rotten_raises(self):
        be = ReplicatedBackend(3, "1.0", [0, 1, 2])
        be.write_objects({"x": b"payload"})
        for s in range(3):
            be.cluster.osd(be.acting[s]).collections[
                shard_cid(be.pg, s)]["x"].data[0] ^= 1
        with pytest.raises(ValueError, match="digest"):
            be.read_object("x")

    def test_repair_pg_fixes_non_primary_rot(self):
        be = ReplicatedBackend(3, "1.0", [0, 1, 2])
        objs = corpus(3, 200, seed=6)
        be.write_objects(objs)
        # rot a NON-primary replica: plain reads never touch it
        st = be.cluster.osd(be.acting[2])
        st.collections[shard_cid(be.pg, 2)]["obj-0"].data[5] ^= 4
        rep = be.repair_pg()
        assert rep["repaired"] >= 1
        assert be.deep_scrub()["inconsistent"] == []


def test_cluster_pg_repair_clears_scrub_report():
    c = make_cluster(pg_num=2)
    objs = corpus(6, 300, seed=7)
    c.write(objs)
    name = next(iter(objs))
    ps = c.locate(name)
    be = c.pgs[ps]
    st = c.cluster.osd(be.acting[1])
    st.collections[shard_cid(be.pg, 1)][name].data[0] ^= 2
    c.scrub_interval = 5.0
    c.deep_scrub_interval = 10.0
    for _ in range(8):
        c.tick(12)
        if ps in c.scrub_reports:
            break
    assert ps in c.scrub_reports
    rep = c.repair_pg(ps)
    assert rep["repaired"] >= 1
    assert ps not in c.scrub_reports
    assert c.verify_all(objs) == len(objs)


class TestReviewRegressions:
    def test_substitute_shard_rot_never_corrupts(self):
        # the EIO decode must verify substitutes: rot on a read shard
        # AND on the would-be substitute must still return exact bytes
        be, cluster = ec_be()
        objs = corpus(3, 400, seed=8)
        be.write_objects(objs)
        rot(cluster, be, 0, "obj-1")          # in the read set
        rot(cluster, be, 4, "obj-1", flip=7)  # likely substitute
        got = be.read_object("obj-1")
        assert np.array_equal(got, objs["obj-1"])
        assert be.deep_scrub()["inconsistent"] == []  # both repaired

    def test_rot_beyond_m_raises_not_corrupts(self):
        be, cluster = ec_be()  # m=2
        be.write_objects(corpus(2, 300, seed=9))
        for s, fl in ((0, 1), (2, 2), (4, 3)):
            rot(cluster, be, s, "obj-0", flip=fl)
        with pytest.raises(ValueError):
            be.read_object("obj-0")

    def test_repair_skips_dead_slots(self):
        be, cluster = ec_be()
        objs = corpus(3, 300, seed=10)
        be.write_objects(objs)
        rot(cluster, be, 1, "obj-0")
        dead_osd = be.acting[1]
        cluster.stores.pop(dead_osd)   # destroyed
        rep = be.repair_pg(dead_osds={dead_osd})
        assert rep["repaired"] == 0
        assert dead_osd not in cluster.stores  # NOT resurrected

    def test_replicated_repair_counts_once(self):
        be = ReplicatedBackend(3, "1.0", [0, 1, 2])
        be.write_objects(corpus(2, 200, seed=11))
        st = be.cluster.osd(be.acting[0])
        st.collections[shard_cid(be.pg, 0)]["obj-1"].data[0] ^= 1
        rep = be.repair_pg()
        assert rep["repaired"] + be.eio_stats["repaired"] >= 1
        assert be.eio_stats["repaired"] == 1  # exactly one rewrite
        assert be.deep_scrub()["inconsistent"] == []

    def test_length_rot_fails_over(self):
        be = ReplicatedBackend(3, "1.0", [0, 1, 2])
        objs = corpus(2, 250, seed=12)
        be.write_objects(objs)
        obj = be.cluster.osd(be.acting[0]).collections[
            shard_cid(be.pg, 0)]["obj-0"]
        obj.data = obj.data[:100].copy()   # truncation rot
        got = be.read_object("obj-0")
        assert np.array_equal(got, objs["obj-0"])
        assert be.deep_scrub()["inconsistent"] == []
