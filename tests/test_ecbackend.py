"""MemStore + ECBackend tests: transactional store semantics, EC
write/read round-trips, degraded reads, batched recovery, deep scrub —
the hermetic recovery pipeline (mirrors store_test.cc + the standalone
erasure-code cluster tests' assertions, in-process)."""

import numpy as np
import pytest

from ceph_tpu.osd.ecbackend import ECBackend, HINFO_KEY, ShardSet, shard_cid
from ceph_tpu.osd.memstore import MemStore, Transaction


# ------------------------------------------------------------- MemStore

class TestMemStore:
    def test_write_read_roundtrip(self):
        st = MemStore()
        st.queue_transaction(Transaction().create_collection("c"))
        st.queue_transaction(Transaction().write("c", "o", 0, b"hello"))
        assert st.read("c", "o").tobytes() == b"hello"
        st.queue_transaction(Transaction().write("c", "o", 3, b"XYZ"))
        assert st.read("c", "o").tobytes() == b"helXYZ"

    def test_atomicity_on_invalid_op(self):
        st = MemStore()
        st.queue_transaction(Transaction().create_collection("c"))
        t = (Transaction().write("c", "o", 0, b"data")
             .write("nope", "o", 0, b"x"))
        with pytest.raises(KeyError):
            st.queue_transaction(t)
        assert not st.exists("c", "o")  # nothing applied

    def test_truncate_grow_shrink(self):
        st = MemStore()
        st.queue_transaction(Transaction().create_collection("c"))
        st.queue_transaction(Transaction().write("c", "o", 0, b"abcdef"))
        st.queue_transaction(Transaction().truncate("c", "o", 3))
        assert st.read("c", "o").tobytes() == b"abc"
        st.queue_transaction(Transaction().truncate("c", "o", 5))
        assert st.read("c", "o").tobytes() == b"abc\x00\x00"

    def test_xattr_omap_remove(self):
        st = MemStore()
        st.queue_transaction(
            Transaction().create_collection("c").touch("c", "o")
            .setattr("c", "o", "k", b"v").omap_set("c", "o", {b"a": b"1"}))
        assert st.getattr("c", "o", "k") == b"v"
        st.queue_transaction(Transaction().remove("c", "o"))
        assert not st.exists("c", "o")
        assert st.list_objects("c") == []


# ------------------------------------------------------------- ECBackend

def make_backend(profile="plugin=tpu_rs k=4 m=2",
                 n_osds=6, chunk_size=256):
    cluster = ShardSet()
    be = ECBackend(profile, "1.0", list(range(n_osds)), cluster,
                   chunk_size=chunk_size)
    return be, cluster


def write_corpus(be, n=20, size=900, seed=0):
    rng = np.random.default_rng(seed)
    objs = {f"obj{i}": rng.integers(0, 256, size=size, dtype=np.uint8)
            for i in range(n)}
    be.write_objects({k: v for k, v in objs.items()})
    return objs


class TestECBackend:
    def test_write_read_roundtrip(self):
        be, _ = make_backend()
        objs = write_corpus(be)
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)

    def test_shards_land_on_stores_with_hinfo(self):
        be, cluster = make_backend()
        write_corpus(be, n=3)
        for shard in range(be.n):
            store = cluster.osd(be.acting[shard])
            names = store.list_objects(shard_cid("1.0", shard))
            assert len(names) == 3
            for nm in names:
                assert store.getattr(shard_cid("1.0", shard), nm, HINFO_KEY)

    def test_degraded_read(self):
        be, _ = make_backend()
        objs = write_corpus(be)
        # two dead osds (= m): still readable via decode
        got = be.read_objects(list(objs), dead_osds={0, 3})
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)
        with pytest.raises(ValueError):
            be.read_objects(list(objs), dead_osds={0, 1, 3})

    def test_recovery_rebuilds_lost_shard_bit_exact(self):
        be, cluster = make_backend()
        objs = write_corpus(be, n=30)
        # capture shard 1 bytes, kill its osd, recover onto osd 17
        before = {n: cluster.osd(1).read(shard_cid("1.0", 1), n)
                  for n in sorted(objs)}
        cluster.stores.pop(1)
        counters = be.recover_shards([1], replacement_osds={1: 17})
        assert counters["objects"] == 30
        assert counters["hinfo_failures"] == 0
        for n in sorted(objs):
            after = cluster.osd(17).read(shard_cid("1.0", 1), n)
            np.testing.assert_array_equal(after, before[n], err_msg=n)
        # reads now work with no special casing
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data)

    def test_recovery_two_shards(self):
        be, cluster = make_backend()
        objs = write_corpus(be, n=10)
        cluster.stores.pop(0)
        cluster.stores.pop(5)
        counters = be.recover_shards([0, 5],
                                     replacement_osds={0: 20, 5: 21})
        assert counters["objects"] == 10
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data)

    def test_recovery_detects_corrupt_helper(self):
        be, cluster = make_backend()
        objs = write_corpus(be, n=4)
        # corrupt one helper shard byte behind the backend's back
        st = cluster.osd(2)
        st.queue_transaction(
            Transaction().write(shard_cid("1.0", 2), "obj0", 5, b"\xFF"))
        cluster.stores.pop(1)
        counters = be.recover_shards([1], replacement_osds={1: 9})
        assert counters["hinfo_failures"] >= 1

    def test_deep_scrub_clean_and_dirty(self):
        be, cluster = make_backend()
        write_corpus(be, n=5)
        rep = be.deep_scrub()
        assert rep["checked"] == 5 * be.n
        assert rep["inconsistent"] == []
        st = cluster.osd(3)
        st.queue_transaction(
            Transaction().write(shard_cid("1.0", 3), "obj2", 0, b"\x00\x01"))
        rep = be.deep_scrub()
        assert ("obj2", 3) in rep["inconsistent"]

    def test_clay_backend_end_to_end(self):
        be, cluster = make_backend(
            profile="plugin=clay k=4 m=2 d=5 impl=ref", chunk_size=None)
        objs = write_corpus(be, n=6, size=2000)
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data)
        cluster.stores.pop(2)
        be.recover_shards([2], replacement_osds={2: 30})
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data)

    def test_mixed_object_sizes(self):
        be, _ = make_backend()
        rng = np.random.default_rng(3)
        objs = {f"o{i}": rng.integers(0, 256, size=sz, dtype=np.uint8)
                for i, sz in enumerate([10, 1000, 4096, 777])}
        be.write_objects(dict(objs))
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)


class TestReviewRegressions:
    def test_overwrite_with_smaller_object(self):
        be, _ = make_backend()
        rng = np.random.default_rng(8)
        big = rng.integers(0, 256, size=4096, dtype=np.uint8)
        small = rng.integers(0, 256, size=900, dtype=np.uint8)
        be.write_objects({"o": big})
        be.write_objects({"o": small})
        np.testing.assert_array_equal(be.read_object("o"), small)
        assert be.deep_scrub()["inconsistent"] == []

    def test_corrupt_helper_does_not_poison_rebuild(self):
        be, cluster = make_backend()
        objs = write_corpus(be, n=4)
        st = cluster.osd(2)
        st.queue_transaction(
            Transaction().write(shard_cid("1.0", 2), "obj0", 5, b"\xFF"))
        cluster.stores.pop(1)
        counters = be.recover_shards([1], replacement_osds={1: 9})
        assert counters["hinfo_failures"] >= 1
        # rebuilt shard must be byte-correct despite the corrupt helper
        got = be.read_objects(list(objs), dead_osds={2})
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)
        rep = be.deep_scrub()
        assert ("obj0", 1) not in rep["inconsistent"]  # no laundering
        assert ("obj0", 2) in rep["inconsistent"]      # real corruption seen

    def test_rmattr_missing_object_is_atomic_noop(self):
        st = MemStore()
        st.queue_transaction(Transaction().create_collection("c"))
        st.queue_transaction(
            Transaction().write("c", "a", 0, b"x").rmattr("c", "missing", "k"))
        assert st.exists("c", "a")  # whole txn applied

    def test_negative_write_offset_rejected_before_apply(self):
        st = MemStore()
        st.queue_transaction(Transaction().create_collection("c"))
        with pytest.raises(ValueError):
            Transaction().write("c", "a", 0, b"x").write("c", "b", -2, b"xyz")
        assert not st.exists("c", "a")

    def test_zero_length_object(self):
        be, _ = make_backend()
        be.write_objects({"empty": b"", "full": b"hello world"})
        assert be.read_object("empty").size == 0
        assert be.read_object("full").tobytes() == b"hello world"
        assert be.deep_scrub()["inconsistent"] == []
        # recovery with an empty object in the corpus
        be.cluster.stores.pop(1)
        be.recover_shards([1], replacement_osds={1: 8})
        assert be.read_object("empty").size == 0
        assert be.deep_scrub()["inconsistent"] == []


class TestFusedLrcClayRecovery:
    """LRC/Clay recovery must take the fused CRC+decode launch path
    (batch_decoder), not the generic per-launch decode_chunks loop —
    exactly the codecs whose repair efficiency is their reason to
    exist (r4 verdict item 2; ref: ErasureCodeLrc::minimum_to_decode,
    ErasureCodeClay::decode_layered)."""

    def _assert_fused_recovery(self, profile, lose_slot, n_objs=6,
                               size=1500):
        from ceph_tpu.ec.registry import factory
        coder = factory(profile)
        n = coder.get_chunk_count()
        cluster = ShardSet()
        be = ECBackend(profile, "1.0", list(range(n)), cluster,
                       chunk_size=256)
        objs = write_corpus(be, n=n_objs, size=size)
        survivors = [s for s in range(n) if s != lose_slot]
        helper = sorted(be.coder.minimum_to_decode([lose_slot],
                                                   survivors))
        assert be.coder.batch_decoder([lose_slot], helper) is not None
        # the generic path must NOT be taken: a decode_chunks call
        # during recovery means the fused path regressed
        def boom(*a, **kw):
            raise AssertionError("generic decode_chunks path taken")
        orig = be.coder.decode_chunks
        be.coder.decode_chunks = boom
        try:
            cluster.stores.pop(lose_slot)
            counters = be.recover_shards([lose_slot],
                                         replacement_osds={lose_slot: 90})
        finally:
            be.coder.decode_chunks = orig
        assert counters["objects"] == n_objs
        assert counters["hinfo_failures"] == 0
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)
        return helper

    def test_lrc_single_loss_fused_and_local(self):
        helper = self._assert_fused_recovery("plugin=lrc k=8 m=4 l=4",
                                             lose_slot=1)
        # the fused plan still honors locality: l helpers, not k
        assert len(helper) == 4

    def test_lrc_parity_loss_fused(self):
        self._assert_fused_recovery("plugin=lrc k=8 m=4 l=4",
                                    lose_slot=0)

    def test_clay_single_loss_fused_d_helpers(self):
        helper = self._assert_fused_recovery(
            "plugin=clay k=4 m=2 d=5", lose_slot=2)
        assert len(helper) == 5

    def test_clay_multi_loss_falls_back(self):
        """Two losses have no static single-chunk repair matrix: the
        generic path must still recover bit-exact."""
        profile = "plugin=clay k=4 m=2 d=5"
        from ceph_tpu.ec.registry import factory
        n = factory(profile).get_chunk_count()
        cluster = ShardSet()
        be = ECBackend(profile, "1.0", list(range(n)), cluster,
                       chunk_size=256)
        objs = write_corpus(be, n=4, size=1200)
        cluster.stores.pop(0)
        cluster.stores.pop(3)
        be.recover_shards([0, 3], replacement_osds={0: 70, 3: 71})
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data)


class TestNonIdentityChunkMapping:
    """LRC's interleaved data/parity positions exercise
    get_chunk_mapping end-to-end: write, degraded read, RMW overwrite,
    EIO repair — all under a non-identity slot permutation (r4 verdict
    item 6; ref: ErasureCodeInterface::get_chunk_mapping)."""

    PROFILE = "plugin=lrc k=4 m=2 l=3"

    def _mk(self):
        from ceph_tpu.ec.registry import factory
        n = factory(self.PROFILE).get_chunk_count()
        cluster = ShardSet()
        be = ECBackend(self.PROFILE, "1.0", list(range(n)), cluster,
                       chunk_size=256)
        assert be.chunk_mapping != list(range(be.n)), \
            "profile no longer exercises a non-identity mapping"
        return be, cluster

    def test_write_read_roundtrip(self):
        be, _ = self._mk()
        objs = write_corpus(be, n=6, size=1100)
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)

    def test_degraded_read_data_slot_down(self):
        be, cluster = self._mk()
        objs = write_corpus(be, n=4, size=900)
        # take down the slot carrying dense data row 0 (not slot 0 —
        # under LRC's mapping they differ)
        slot = be.data_slots[0]
        got = be.read_objects(list(objs),
                              dead_osds={be.acting[slot]})
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)

    def test_rmw_overwrite_and_extend(self):
        be, _ = self._mk()
        rng = np.random.default_rng(9)
        base = rng.integers(0, 256, 2000, np.uint8)
        be.write_objects({"o": base})
        patch = rng.integers(0, 256, 333, np.uint8)
        be.write_at("o", 700, patch)
        want = base.copy()
        want[700:700 + 333] = patch
        np.testing.assert_array_equal(be.read_objects(["o"])["o"], want)
        tail = rng.integers(0, 256, 500, np.uint8)
        be.write_at("o", 1900, tail)   # extends past the old end
        want = np.concatenate([want[:1900], tail])
        np.testing.assert_array_equal(be.read_objects(["o"])["o"], want)

    def test_eio_repair_under_mapping(self):
        be, cluster = self._mk()
        objs = write_corpus(be, n=3, size=800)
        slot = be.data_slots[1]
        st = cluster.osd(be.acting[slot])
        st.queue_transaction(Transaction().write(
            shard_cid("1.0", slot), "obj1", 3, b"\xAA\xBB"))
        got = be.read_objects(list(objs))
        np.testing.assert_array_equal(got["obj1"], objs["obj1"])
        assert be.eio_stats["read_eio"] >= 1
        assert be.eio_stats["repaired"] >= 1

    def test_a_fused_write_hands_the_fanout_views_in_slot_order(self):
        """The layers composed make LRC's write one fused launch, and its
        rows reach the fan-out in slot order as views of the data and
        parity rows: no copy into dense order, none into slot order."""
        be, _ = self._mk()
        data = np.random.default_rng(4031).integers(0, 256,
                                                    (2, be.k, 512), np.uint8)
        shards, crcs = be._encode_shards_with_crcs(data, 512)
        assert be.perf.get("fused_write_launches") == 1
        assert be.perf.get("encode_launches") == 0
        for j, slot in enumerate(be.data_slots):
            assert np.shares_memory(shards[1, slot, :], data)
            np.testing.assert_array_equal(shards[1, slot, :], data[1, j])
        dense = np.concatenate(
            [data, np.asarray(be.coder.encode_chunks(data))], axis=1)
        want = be._slots_from_dense(dense)
        np.testing.assert_array_equal(np.asarray(shards), want)
        np.testing.assert_array_equal(crcs, be._batched_hinfo_crcs(
            want.reshape(-1, 512)).reshape(2, be.n))


class TestStraySweep:
    def test_repair_removes_unknown_leftovers(self):
        """Objects a store holds that the PG metadata doesn't know
        (e.g. a non-primary rejoiner's divergent dead-interval
        leftovers) are removed by `pg repair`, and deep scrub doesn't
        crash on their missing hinfo (r5 review finding)."""
        be, cluster = make_backend()
        objs = write_corpus(be, n=5)
        st = cluster.osd(2)
        st.queue_transaction(Transaction().write(
            shard_cid("1.0", 2), "ghost-leftover", 0, b"Z" * 64))
        rep = be.deep_scrub()          # must not raise on the stray
        assert rep["inconsistent"] == []
        out = be.repair_pg()
        assert out["strays_removed"] == 1
        assert "ghost-leftover" not in st.list_objects(shard_cid("1.0", 2))
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data)


# ------------------------------------------------- the read path's gather

class _Frame:
    """What RemoteStore.readv_submit hands back: the answer is made
    when it is collected, from the wrapped store as a daemon's
    `readv` handler makes it."""

    def __init__(self, store, cid, oids, length, attr_key):
        self.store, self.args = store, (cid, list(oids), length, attr_key)
        store.in_flight.add(self)

    def cancel(self):
        self.store.in_flight.discard(self)
        self.store.cancelled += 1

    def result(self):
        st = self.store
        st.in_flight.discard(self)
        cid, oids, length, attr_key = self.args
        if st.fail is not None:
            raise st.fail
        rows = [MemStore.read(st, cid, o).tobytes() for o in oids]
        attrs = [MemStore.getattr(st, cid, o, attr_key) for o in oids] \
            if attr_key else None
        return b"".join(rows)[:st.cut], attrs


class FramedStore(MemStore):
    """A store reached through frames, as a remote one is: it offers
    `readv_submit`, and its one-at-a-time reads are counted."""

    def __init__(self, log):
        super().__init__()
        self.log, self.in_flight, self.cancelled = log, set(), 0
        self.fail, self.cut = None, None

    def readv_submit(self, cid, oids, length, attr_key=None):
        self.log.append(("readv", cid, tuple(oids), attr_key))
        return _Frame(self, cid, oids, length, attr_key)

    def read(self, cid, oid, offset=0, length=None):
        self.log.append(("read", cid, oid))
        return super().read(cid, oid, offset, length)

    def getattr(self, cid, oid, key):
        self.log.append(("getattr", cid, oid))
        return super().getattr(cid, oid, key)


def framed_backend(local_osd=0, n_osds=6):
    """k=4 m=2 over stores reached by frames, but for `local_osd`,
    which the primary holds itself (a MemStore, read in place)."""
    log: list = []
    cluster = ShardSet(store_factory=lambda osd: MemStore()
                       if osd == local_osd else FramedStore(log))
    be = ECBackend("plugin=tpu_rs k=4 m=2", "1.0",
                   list(range(n_osds)), cluster, chunk_size=256)
    return be, cluster, log


def _counts(be):
    return (int(be.perf.get("gather_rounds")),
            int(be.perf.get("gather_frames")))


class TestGatherRound:
    """ECBackend.read_objects gathers a plan's rows in one round: one
    readv frame a remote slot with the hinfo attr in the same answer,
    the primary's own slot read in place (PR 28)."""

    def test_one_frame_a_remote_slot_and_no_attr_pass(self):
        be, _, log = framed_backend()
        objs = write_corpus(be, n=5, size=900)
        del log[:]
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)
        # data slots 0-3, slot 0 local: three frames, each naming the
        # whole group and asking for the hinfo with the rows
        assert [e[0] for e in log] == ["readv"] * 3
        assert {e[1] for e in log} == {shard_cid("1.0", s)
                                       for s in (1, 2, 3)}
        assert all(e[2] == tuple(objs) and e[3] == HINFO_KEY for e in log)
        assert _counts(be) == (1, 3)

    def test_verify_off_asks_for_no_attr(self):
        be, _, log = framed_backend()
        objs = write_corpus(be, n=2, size=900)
        del log[:]
        got = be.read_objects(list(objs), verify=False)
        np.testing.assert_array_equal(got["obj1"], objs["obj1"])
        assert [e[0] for e in log] == ["readv"] * 3
        assert all(e[3] is None for e in log)

    def test_every_frame_is_out_before_any_is_collected(self, monkeypatch):
        be, cluster, _ = framed_backend()
        objs = write_corpus(be, n=2, size=900)
        most = []
        keep = _Frame.result

        def result(self):
            most.append(sum(len(cluster.osd(o).in_flight)
                            for o in (1, 2, 3)))
            return keep(self)
        monkeypatch.setattr(_Frame, "result", result)
        be.read_objects(list(objs))
        assert most == [3, 2, 1]          # all out, then taken in turn

    @pytest.mark.parametrize("lacking", [2, 0])
    def test_a_slot_that_lacks_the_object_is_planned_around(self, lacking):
        """A remote slot answers KeyError, or the local store raises
        it: the slot is dropped and the read planned again, after
        the round's other answers are in."""
        be, cluster, log = framed_backend()
        objs = write_corpus(be, n=3, size=900)
        cluster.osd(lacking).queue_transaction(
            Transaction().remove(shard_cid("1.0", lacking), "obj1"))
        before = be.eio_stats["repaired"]
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)
        rounds, frames = _counts(be)
        assert rounds == 2
        # round 1: 3 remote data slots; round 2: a parity slot stands
        # in (for the local slot, beside the three; for a remote one,
        # beside the two left)
        assert frames == (3 + 3 if lacking else 3 + 4)
        assert int(be.perf.get("degraded_reads")) == 3
        assert be.eio_stats["repaired"] == before
        assert all(not cluster.osd(o).in_flight and
                   not cluster.osd(o).cancelled for o in range(1, 6))

    @pytest.mark.parametrize("repair", [True, False])
    def test_a_rotten_row_takes_the_eio_path(self, repair):
        be, cluster, log = framed_backend()
        objs = write_corpus(be, n=3, size=900)
        cid = shard_cid("1.0", 2)
        good = cluster.osd(2).read(cid, "obj1").copy()
        cluster.osd(2).queue_transaction(
            Transaction().write(cid, "obj1", 7, b"\x5a\xa5"))
        rotten = MemStore.read(cluster.osd(2), cid, "obj1").copy()
        assert not np.array_equal(rotten, good)
        got = be.read_objects(list(objs), repair=repair)
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)
        assert be.eio_stats["read_eio"] == 1
        assert int(be.perf.get("read_eio")) == 1
        assert be.eio_stats["repaired"] == (1 if repair else 0)
        np.testing.assert_array_equal(
            MemStore.read(cluster.osd(2), cid, "obj1"),
            good if repair else rotten)

    def test_a_group_over_the_frame_budget_is_split_in_order(
            self, monkeypatch):
        from ceph_tpu.osd import ecbackend
        be, _, log = framed_backend()
        objs = write_corpus(be, n=7, size=900)     # rows of 256 bytes
        monkeypatch.setattr(ecbackend, "RECOVERY_FETCH_BYTES", 3 * 256)
        del log[:]
        got = be.read_objects(list(objs))
        for name, data in objs.items():
            np.testing.assert_array_equal(got[name], data, err_msg=name)
        names = list(objs)
        for s in (1, 2, 3):
            frames = [e[2] for e in log if e[1] == shard_cid("1.0", s)]
            assert frames == [tuple(names[0:3]), tuple(names[3:6]),
                              tuple(names[6:7])]
        assert {e[0] for e in log} == {"readv"}
        assert _counts(be) == (1, 9)

    @pytest.mark.parametrize("cut,said", [(-1, "got 767 bytes"),
                                          (None, "got 1024 bytes")])
    def test_a_short_or_long_answer_fails_loudly(self, cut, said):
        be, cluster, _ = framed_backend()
        objs = write_corpus(be, n=3, size=900)
        if cut is None:
            # the answer carries a row more than was asked for
            cluster.osd(2).readv_submit = lambda cid, oids, ln, key=None: \
                _Frame(cluster.osd(2), cid, list(oids) + ["obj0"], ln, key)
        else:
            cluster.osd(2).cut = cut
        with pytest.raises(ValueError, match="readv: " + said
                           + ", expected 768"):
            be.read_objects(list(objs))
        assert not any(cluster.osd(o).in_flight for o in range(1, 6))

    def test_a_transport_error_surfaces_and_leaves_nothing_in_flight(self):
        be, cluster, _ = framed_backend()
        objs = write_corpus(be, n=2, size=900)
        cluster.osd(1).fail = ConnectionError("rpc to osd.1 timed out")
        with pytest.raises(ConnectionError, match="osd.1 timed out"):
            be.read_objects(list(objs))
        assert not any(cluster.osd(o).in_flight for o in range(1, 6))
        assert cluster.osd(2).cancelled == cluster.osd(3).cancelled == 1

    def test_sim_cluster_reads_through_the_local_branch(self):
        """The in-process cluster's stores are MemStores: no frame is
        sent and the bytes are the same."""
        from ceph_tpu.osd.cluster import SimCluster
        c = SimCluster(n_osds=8, pg_num=2)
        rng = np.random.default_rng(28)
        objs = {f"o{i}": rng.integers(0, 256, 1500, np.uint8)
                for i in range(6)}
        c.write(objs)
        for name, data in objs.items():
            np.testing.assert_array_equal(c.read(name), data)
        rounds = sum(int(be.perf.get("gather_rounds"))
                     for be in c.pgs.values())
        assert rounds == len(objs)
        assert sum(int(be.perf.get("gather_frames"))
                   for be in c.pgs.values()) == 0
