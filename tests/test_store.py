"""ObjectStore suite — ONE contract run against BOTH stores (the
reference's interface parameterization: src/test/objectstore/
store_test.cc runs the same suite over MemStore and BlueStore), plus
TinStore-only durability tests: WAL replay after SIGKILL, torn-tail
truncation, checkpoint cycling, verify-on-read, fsck, and a cluster
kill/revive that REALLY loses RAM (ref: src/os/bluestore/BlueStore.cc
_verify_csum/fsck; qa process-kill thrash semantics)."""

import os
import struct

import numpy as np
import pytest

from ceph_tpu.osd.memstore import MemStore, Transaction
from ceph_tpu.osd.tinstore import TinStore, TinStoreCorruption


@pytest.fixture(params=["mem", "tin", "tin-zlib"])
def store(request, tmp_path):
    if request.param == "mem":
        yield MemStore()
    elif request.param == "tin-zlib":
        # whole contract under inline compression (min_blob=1 so even
        # tiny compressible payloads take the compressed path)
        yield TinStore(str(tmp_path / "tin"), compression="zlib",
                       compression_min_blob=1)
    else:
        yield TinStore(str(tmp_path / "tin"))


def reopen(st):
    """Persistence boundary: for TinStore simulate SIGKILL + remount;
    for MemStore a no-op (its contract is RAM-lifetime only)."""
    if isinstance(st, TinStore):
        st.crash()
        st.remount()
    return st


class TestStoreContract:
    def test_write_read_roundtrip(self, store):
        t = (Transaction().create_collection("c")
             .write("c", "o", 0, b"hello world"))
        store.queue_transaction(t)
        assert bytes(store.read("c", "o")) == b"hello world"
        assert store.stat("c", "o") == 11

    def test_write_extends_with_zeros(self, store):
        store.queue_transaction(
            Transaction().create_collection("c").write("c", "o", 4, b"xy"))
        assert bytes(store.read("c", "o")) == b"\x00\x00\x00\x00xy"

    def test_overwrite_middle(self, store):
        store.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"abcdef").write("c", "o", 2, b"XY"))
        assert bytes(store.read("c", "o")) == b"abXYef"

    def test_truncate_shrink_and_grow(self, store):
        store.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"abcdef").truncate("c", "o", 3))
        assert bytes(store.read("c", "o")) == b"abc"
        store.queue_transaction(Transaction().truncate("c", "o", 5))
        assert bytes(store.read("c", "o")) == b"abc\x00\x00"

    def test_remove_and_touch(self, store):
        store.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"x").remove("c", "o").touch("c", "p"))
        assert not store.exists("c", "o")
        assert store.exists("c", "p")
        assert store.stat("c", "p") == 0

    def test_xattr_and_omap(self, store):
        store.queue_transaction(
            Transaction().create_collection("c").touch("c", "o")
            .setattr("c", "o", "hinfo", b"\x01\x02")
            .omap_set("c", "o", {b"k": b"v"}))
        assert store.getattr("c", "o", "hinfo") == b"\x01\x02"
        store.queue_transaction(Transaction().rmattr("c", "o", "hinfo"))
        with pytest.raises(KeyError):
            store.getattr("c", "o", "hinfo")

    def test_omap_rmkeys_and_clear(self, store):
        """OP_OMAP_RMKEYS / OP_OMAP_CLEAR (ref: src/os/ObjectStore.h):
        KV entries must be removable without killing the object."""
        store.queue_transaction(
            Transaction().create_collection("c").touch("c", "o")
            .omap_set("c", "o", {b"a": b"1", b"b": b"2", b"c": b"3"}))
        store.queue_transaction(
            Transaction().omap_rmkeys("c", "o", [b"a", b"missing"]))
        reopen(store)
        obj = store.collections["c"]["o"]
        assert dict(obj.omap) == {b"b": b"2", b"c": b"3"}
        store.queue_transaction(Transaction().omap_clear("c", "o"))
        reopen(store)
        assert dict(store.collections["c"]["o"].omap) == {}
        assert store.exists("c", "o")

    def test_remove_then_write_in_one_txn(self, store):
        # ops apply IN ORDER: a write after a remove starts from an
        # empty object — the old bytes must not resurrect (r4 review:
        # TinStore staging read pre-txn state)
        store.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"AAAAAAAA"))
        store.queue_transaction(
            Transaction().remove("c", "o").write("c", "o", 0, b"BB"))
        assert bytes(store.read("c", "o")) == b"BB"
        assert store.stat("c", "o") == 2
        reopen(store)
        assert bytes(store.read("c", "o")) == b"BB"

    def test_rmcoll_then_recreate_in_one_txn(self, store):
        store.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"old bytes"))
        store.queue_transaction(
            Transaction().remove_collection("c").create_collection("c")
            .write("c", "o", 3, b"xy"))
        assert bytes(store.read("c", "o")) == b"\x00\x00\x00xy"

    def test_rmcoll_recreate_touch_keeps_no_old_record(self, store):
        # the re-created collection is empty in the KV plane too: the
        # touched object is new, not the pre-txn record resurrected
        store.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"old bytes").setattr("c", "o", "a", b"1"))
        store.queue_transaction(
            Transaction().remove_collection("c").create_collection("c")
            .touch("c", "o"))
        reopen(store)
        assert store.stat("c", "o") == 0
        assert dict(store.collections["c"]["o"].xattrs) == {}

    def test_omap_rmkeys_missing_object_is_noop(self, store):
        store.queue_transaction(Transaction().create_collection("c"))
        store.queue_transaction(
            Transaction().omap_rmkeys("c", "ghost", [b"k"])
            .omap_clear("c", "ghost"))
        assert not store.exists("c", "ghost")

    def test_collections_listing(self, store):
        store.queue_transaction(
            Transaction().create_collection("b").create_collection("a")
            .write("a", "z", 0, b"1").write("a", "y", 0, b"2"))
        assert store.list_collections() == ["a", "b"]
        assert store.list_objects("a") == ["y", "z"]
        store.queue_transaction(Transaction().remove_collection("b"))
        assert store.list_collections() == ["a"]

    def test_validation_aborts_whole_txn(self, store):
        store.queue_transaction(Transaction().create_collection("c"))
        bad = (Transaction().write("c", "o", 0, b"data")
               .write("nope", "o", 0, b"data"))
        with pytest.raises(KeyError):
            store.queue_transaction(bad)
        # all-or-nothing: the eligible first op must NOT have applied
        assert not store.exists("c", "o")

    def test_missing_reads_raise(self, store):
        with pytest.raises(KeyError):
            store.read("c", "o")
        store.queue_transaction(Transaction().create_collection("c"))
        with pytest.raises(KeyError):
            store.read("c", "o")


class TestTinStoreDurability:
    def test_kill_loses_nothing_committed(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"committed bytes")
            .setattr("c", "o", "a", b"xattr")
            .omap_set("c", "o", {b"k": b"v"}))
        st.crash()                      # SIGKILL: RAM gone
        with pytest.raises(RuntimeError):
            st.read("c", "o")
        st.remount()                    # recovery = WAL replay only
        assert bytes(st.read("c", "o")) == b"committed bytes"
        assert st.getattr("c", "o", "a") == b"xattr"
        assert st.committed_txns == 1

    def test_many_txns_replay_in_order(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(Transaction().create_collection("c"))
        rng = np.random.default_rng(3)
        want = {}
        for i in range(40):
            data = rng.integers(0, 256, int(rng.integers(1, 400)),
                                np.uint8)
            name = f"o{i % 7}"         # overwrites interleave creates
            st.queue_transaction(
                Transaction().write("c", name, 0, data)
                .truncate("c", name, len(data)))
            want[name] = data.tobytes()
        reopen(st)
        for name, data in want.items():
            assert bytes(st.read("c", name)) == data

    def test_torn_tail_record_dropped(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c").write("c", "o", 0, b"ok"))
        st.crash()
        # simulate crash mid-append: garbage half-record at the tail
        with open(os.path.join(str(tmp_path / "s"), "wal.log"), "ab") as f:
            f.write(struct.pack("<IQI", 0x544E4952, 99, 1 << 20))
            f.write(b"\x01\x02\x03")    # body cut short
        st.remount()
        assert bytes(st.read("c", "o")) == b"ok"
        # the torn bytes were truncated away; new commits extend cleanly
        st.queue_transaction(Transaction().write("c", "p", 0, b"post"))
        reopen(st)
        assert bytes(st.read("c", "p")) == b"post"

    def test_mid_log_corruption_fails_loudly(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c").write("c", "a", 0, b"1"))
        st.queue_transaction(Transaction().write("c", "b", 0, b"2"))
        st.crash()
        wal = os.path.join(str(tmp_path / "s"), "wal.log")
        with open(wal, "r+b") as f:
            f.seek(20)                  # inside record 1's body
            f.write(b"\xff\xff")
        with pytest.raises(TinStoreCorruption):
            st.remount()
        rep = TinStore.fsck(str(tmp_path / "s"))
        assert rep["errors"]

    def test_checkpoint_cycle_and_recovery(self, tmp_path):
        st = TinStore(str(tmp_path / "s"), wal_max_bytes=2000)
        st.queue_transaction(Transaction().create_collection("c"))
        rng = np.random.default_rng(5)
        want = {}
        for i in range(30):             # crosses several checkpoints
            data = rng.integers(0, 256, 150, np.uint8)
            st.queue_transaction(Transaction().write("c", f"o{i}", 0, data))
            want[f"o{i}"] = data.tobytes()
        # crossing wal_max_bytes flushed the KV memtable to at least
        # one sorted segment under the (crc-sealed) MANIFEST
        assert os.path.exists(os.path.join(str(tmp_path / "s"), "MANIFEST"))
        ks = st.kv_stats()
        assert ks["flushes"] >= 1 and ks["segments"] >= 1
        reopen(st)
        for name, data in want.items():
            assert bytes(st.read("c", name)) == data
        assert st.committed_txns == 31

    def test_umount_checkpoint_then_clean_mount(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c").write("c", "o", 0, b"z"))
        st.umount()
        # after umount the WAL is empty; state lives in the checkpoint
        assert os.path.getsize(
            os.path.join(str(tmp_path / "s"), "wal.log")) == 0
        st.remount()
        assert bytes(st.read("c", "o")) == b"z"

    def test_verify_on_read_catches_ram_rot(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"clean bytes"))
        st.collections["c"]["o"].data[3] ^= 0x40    # bypasses the WAL
        with pytest.raises(TinStoreCorruption):
            st.read("c", "o")

    def test_segment_corruption_detected_at_mount(self, tmp_path):
        # umount flushes the memtable into a sealed segment; flip a
        # byte inside it — the seal must fail the next mount AND fsck
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"will be sealed"))
        st.umount()
        segs = [f for f in os.listdir(str(tmp_path / "s"))
                if f.startswith("seg-") and f.endswith(".tdb")]
        assert segs, "umount should have flushed a segment"
        with open(os.path.join(str(tmp_path / "s"), segs[0]),
                  "r+b") as f:
            f.seek(12)
            f.write(b"\xaa")
        with pytest.raises(TinStoreCorruption):
            st.remount()
        rep = TinStore.fsck(str(tmp_path / "s"))
        assert rep["errors"]

    def test_manifest_corruption_detected_at_mount(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o", 0, b"manifest guard"))
        st.umount()
        with open(os.path.join(str(tmp_path / "s"), "MANIFEST"),
                  "r+b") as f:
            f.seek(6)
            f.write(b"\xaa")
        with pytest.raises(TinStoreCorruption):
            st.remount()
        rep = TinStore.fsck(str(tmp_path / "s"))
        assert rep["errors"]

    def test_fsck_clean_report(self, tmp_path):
        st = TinStore(str(tmp_path / "s"), wal_max_bytes=10 << 20)
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "o1", 0, b"abc").write("c", "o2", 0, b"def"))
        st.queue_transaction(Transaction().write("c", "o3", 0, b"ghi"))
        st.crash()
        rep = TinStore.fsck(str(tmp_path / "s"))
        assert rep["objects"] == 3 and rep["wal_records"] == 2
        assert not rep["bad_objects"] and not rep["errors"]
        assert not rep["torn_tail"] and not rep["extent_errors"]
        # 3 objects × one 4 KiB allocation unit each, all accounted
        assert rep["used_bytes"] == 3 * 4096
        assert rep["device_bytes"] >= rep["used_bytes"]


class TestTinStoreBlockPlane:
    """The block-device plane (ref: src/os/bluestore/BlueStore.cc
    _do_read cache path, BitmapAllocator): bounded cache, extent
    allocator reuse, metadata-only checkpoints."""

    def test_bounded_cache_serves_4x_dataset(self, tmp_path):
        # 64 objects x 16 KiB = 1 MiB working set through a 256 KiB
        # cache: every byte must serve exactly, the budget must hold,
        # and eviction must force device reads
        budget = 256 << 10
        st = TinStore(str(tmp_path / "s"), cache_bytes=budget)
        rng = np.random.default_rng(7)
        objs = {f"o{i:02d}": rng.integers(0, 256, 16384,
                                          np.uint8).tobytes()
                for i in range(64)}
        t = Transaction().create_collection("c")
        for name, data in objs.items():
            t.write("c", name, 0, data)
        st.queue_transaction(t)
        for _ in range(2):
            for name, want in objs.items():
                assert bytes(st.read("c", name)) == want
                assert st.cache_stats()["bytes"] <= budget
        assert st.cache_stats()["misses"] > 0
        st.crash()
        st.remount()
        for name, want in objs.items():
            assert bytes(st.read("c", name)) == want
            assert st.cache_stats()["bytes"] <= budget

    def test_checkpoint_is_metadata_only(self, tmp_path):
        # 4 MiB of object data; the flushed KV plane must stay tiny
        # (extent refs, not bytes) — the r3 O(store) serialize is gone
        st = TinStore(str(tmp_path / "s"))
        big = bytes(range(256)) * (4 << 12)
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "big", 0, big))
        st.checkpoint()
        d = str(tmp_path / "s")
        kv_bytes = sum(
            os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)
            if f == "MANIFEST" or f.endswith(".tdb"))
        assert kv_bytes < 16 << 10, \
            f"KV plane {kv_bytes}B should be metadata-only"
        st.crash()
        st.remount()
        assert bytes(st.read("c", "big")) == big

    def test_extent_reuse_bounds_device_growth(self, tmp_path):
        # repeated COW overwrites recycle freed extents: the device
        # must not grow linearly with write count
        st = TinStore(str(tmp_path / "s"))
        data = bytes(range(256)) * 64          # 16 KiB
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "a", 0, data))
        for _ in range(16):
            st.queue_transaction(Transaction().write("c", "a", 0, data))
        dev = os.path.getsize(os.path.join(str(tmp_path / "s"),
                                           "block.dev"))
        # steady state: live extent + one COW scratch extent
        assert dev <= 2 * len(data) + 4096, f"device grew to {dev}"
        rep = TinStore.fsck(str(tmp_path / "s"))
        assert rep["used_bytes"] == 16384 and not rep["extent_errors"]

    def test_remove_returns_space(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        data = bytes(64 << 10)
        t = Transaction().create_collection("c")
        for i in range(4):
            t.write("c", f"o{i}", 0, data)
        st.queue_transaction(t)
        used0 = st._alloc.used_bytes()
        t = Transaction()
        for i in range(4):
            t.remove("c", f"o{i}")
        st.queue_transaction(t)
        assert st._alloc.used_bytes() == 0 and used0 == 4 * (64 << 10)
        # freed space is reused, not appended after
        st.queue_transaction(Transaction().write("c", "n", 0, data))
        assert st._alloc.used_bytes() == 64 << 10
        dev = os.path.getsize(os.path.join(str(tmp_path / "s"),
                                           "block.dev"))
        assert dev <= 4 * (64 << 10)

    def test_derived_allocator_survives_crash(self, tmp_path):
        # allocations are not persisted: after SIGKILL the allocator
        # rebuilds from the extent map and audits cleanly
        st = TinStore(str(tmp_path / "s"))
        rng = np.random.default_rng(3)
        t = Transaction().create_collection("c")
        for i in range(10):
            t.write("c", f"o{i}", 0,
                    rng.integers(0, 256, 5000 + 117 * i,
                                 np.uint8).tobytes())
        st.queue_transaction(t)
        st.queue_transaction(
            Transaction().remove("c", "o3").remove("c", "o7"))
        used = st._alloc.used_bytes()
        st.crash()
        st.remount()
        assert st._alloc.used_bytes() == used
        rep = TinStore.fsck(str(tmp_path / "s"))
        assert not rep["extent_errors"] and rep["used_bytes"] == used

    def test_omap_rmkeys_survive_crash_replay(self, tmp_path):
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(
            Transaction().create_collection("c").touch("c", "o")
            .omap_set("c", "o", {b"a": b"1", b"b": b"2"}))
        st.queue_transaction(Transaction().omap_rmkeys("c", "o", [b"a"]))
        st.crash()                 # rmkeys lives only in the WAL tail
        st.remount()
        assert dict(st.collections["c"]["o"].omap) == {b"b": b"2"}


class TestTinStoreCluster:
    """SimCluster on the persistent store: kill really drops RAM."""

    def _mk(self, tmp_path, **kw):
        from ceph_tpu.osd.cluster import SimCluster
        kw.setdefault("down_out_interval", 600.0)
        return SimCluster(n_osds=8, pg_num=4, store="tin",
                          store_dir=str(tmp_path / "osds"), **kw)

    def test_kill_revive_recovers_from_disk(self, tmp_path):
        from ceph_tpu.client.objecter import Objecter
        c = self._mk(tmp_path)
        ob = Objecter(c)
        rng = np.random.default_rng(7)
        objs = {f"obj{i}": rng.integers(0, 256, 500, np.uint8).tobytes()
                for i in range(12)}
        ob.write(objs)
        victim = c.pgs[0].acting[0]
        c.kill_osd(victim)
        # the victim's RAM state is genuinely gone
        with pytest.raises(RuntimeError):
            c.cluster.stores[victim].read("anything", "at-all")
        c.tick(30.0)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want      # degraded reads
        c.revive_osd(victim)                            # WAL remount
        c.tick(30.0)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
        for ps in range(c.pg_num):
            rep = c.pgs[ps].deep_scrub(dead_osds=c._dead_osds())
            assert rep["inconsistent"] == []

    def test_writes_while_down_replay_onto_revived_store(self, tmp_path):
        from ceph_tpu.client.objecter import Objecter
        c = self._mk(tmp_path)
        ob = Objecter(c)
        rng = np.random.default_rng(8)
        first = {f"a{i}": rng.integers(0, 256, 300, np.uint8).tobytes()
                 for i in range(6)}
        ob.write(first)
        victim = c.pgs[0].acting[1]
        c.kill_osd(victim)
        c.tick(30.0)
        second = {f"b{i}": rng.integers(0, 256, 300, np.uint8).tobytes()
                  for i in range(6)}
        ob.write(second)                 # lands degraded
        c.revive_osd(victim)             # delta replay catches the shard up
        c.tick(30.0)
        for name, want in {**first, **second}.items():
            assert ob.read(name).tobytes() == want
        # and the catch-up is durable: kill + remount again, re-verify
        c.kill_osd(victim)
        c.revive_osd(victim)
        c.tick(30.0)
        for name, want in {**first, **second}.items():
            assert ob.read(name).tobytes() == want

    def test_destroy_removes_disk_and_rebuild_lands_elsewhere(
            self, tmp_path):
        from ceph_tpu.client.objecter import Objecter
        c = self._mk(tmp_path, down_out_interval=30.0)
        ob = Objecter(c)
        rng = np.random.default_rng(9)
        objs = {f"o{i}": rng.integers(0, 256, 400, np.uint8).tobytes()
                for i in range(10)}
        ob.write(objs)
        victim = c.pgs[0].acting[0]
        vdir = os.path.join(c.store_dir, f"osd.{victim}")
        assert os.path.isdir(vdir)
        c.destroy_osd(victim)
        assert not os.path.exists(vdir)  # disk files really deleted
        c.tick(40.0)                     # down -> out -> re-place
        for _ in range(120):
            if not c.backfills:
                break
            c.tick(6.0)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want


class TestTinStoreCompression:
    """Inline compression (ref: BlueStore bluestore_compression_*
    decision + per-blob compressed_length; csum over stored bytes)."""

    def _mk(self, tmp_path, **kw):
        kw.setdefault("compression", "zlib")
        return TinStore(str(tmp_path / "tc"), **kw)

    def test_compressible_shrinks_device_usage(self, tmp_path):
        st = self._mk(tmp_path)
        data = b"ABCD" * 64 * 1024                    # 256 KiB, ratio ~0
        st.queue_transaction(Transaction().create_collection("c")
                             .write("c", "o", 0, data))
        assert bytes(st.read("c", "o")) == data
        s = st.compress_stats
        assert s["compressed_blobs"] == 1
        assert s["stored_bytes"] < len(data) // 10
        # the extent map footprint matches the compressed size
        o = st._meta["c"]["o"]
        assert o.calg == "zlib" and o.clen < len(data) // 10
        assert o.size == len(data)                    # logical size kept

    def test_incompressible_stays_raw(self, tmp_path):
        st = self._mk(tmp_path)
        rng = np.random.default_rng(9)
        data = rng.integers(0, 256, 64 * 1024, np.uint8).tobytes()
        st.queue_transaction(Transaction().create_collection("c")
                             .write("c", "o", 0, data))
        o = st._meta["c"]["o"]
        assert o.calg == "" and st.compress_stats["raw_blobs"] >= 1
        assert bytes(st.read("c", "o")) == data

    def test_below_min_blob_stays_raw(self, tmp_path):
        st = self._mk(tmp_path, compression_min_blob=4096)
        st.queue_transaction(Transaction().create_collection("c")
                             .write("c", "o", 0, b"A" * 1000))
        assert st._meta["c"]["o"].calg == ""

    def test_crash_remount_preserves_compressed_objects(self, tmp_path):
        st = self._mk(tmp_path)
        data = bytes(range(256)) * 2048               # 512 KiB
        st.queue_transaction(Transaction().create_collection("c")
                             .write("c", "o", 0, data))
        st.crash()
        st.remount()
        assert bytes(st.read("c", "o")) == data
        assert st._meta["c"]["o"].calg == "zlib"      # WAL replay kept it
        # and across a checkpoint cycle too
        st.checkpoint()
        st.crash()
        st.remount()
        assert bytes(st.read("c", "o")) == data
        assert st._meta["c"]["o"].calg == "zlib"

    def test_poke_compressed_stream_detected(self, tmp_path):
        st = self._mk(tmp_path)
        data = b"payload " * 32 * 1024
        st.queue_transaction(Transaction().create_collection("c")
                             .write("c", "o", 0, data))
        view = st.collections["c"]["o"].data
        assert len(view) == st._meta["c"]["o"].clen   # stored stream
        view[len(view) // 2] ^= 0xFF
        view.flush()
        with pytest.raises(TinStoreCorruption):
            st.read("c", "o")
        # fsck sees the same damage offline
        st.umount()
        rep = TinStore.fsck(str(tmp_path / "tc"))
        assert rep["bad_objects"] == ["c/o"]

    def test_lzma_roundtrip(self, tmp_path):
        st = self._mk(tmp_path, compression="lzma")
        data = b"lzma lane " * 20000
        st.queue_transaction(Transaction().create_collection("c")
                             .write("c", "o", 0, data))
        assert st._meta["c"]["o"].calg == "lzma"
        st.crash(); st.remount()
        assert bytes(st.read("c", "o")) == data

    def test_bad_alg_refused(self, tmp_path):
        with pytest.raises(ValueError, match="unknown compression"):
            TinStore(str(tmp_path / "x"), compression="snappy")

    def test_compressed_cluster_kill_revive(self, tmp_path):
        """The whole EC/recovery pipeline over COMPRESSED stores:
        shard bytes (highly compressible corpus) survive SIGKILL +
        WAL remount, decompressing bit-exact through degraded reads
        and deep scrub."""
        from ceph_tpu.client.objecter import Objecter
        from ceph_tpu.osd.cluster import SimCluster
        c = SimCluster(n_osds=8, pg_num=4, store="tin",
                       store_dir=str(tmp_path / "osds"),
                       store_compression="zlib",
                       down_out_interval=600.0)
        ob = Objecter(c)
        objs = {f"cz{i}": (f"block {i} " * 600).encode()
                for i in range(10)}
        ob.write(objs)
        assert any(st.compress_stats["compressed_blobs"] > 0
                   for st in c.cluster.stores.values())
        victim = c.pgs[0].acting[0]
        c.kill_osd(victim)
        c.tick(30.0)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
        c.revive_osd(victim)
        c.tick(30.0)
        for name, want in objs.items():
            assert ob.read(name).tobytes() == want
        for ps in range(c.pg_num):
            rep = c.pgs[ps].deep_scrub(dead_osds=c._dead_osds())
            assert rep["inconsistent"] == []


class TestLegacyForwardReplay:
    """Pre-KV stores (v3 `ckpt` checkpoint + metadata-op WAL) must
    mount on the KV TinStore: migration forward-replays them into
    TinDB's first segment and lands the MANIFEST atomically. Nothing
    readable before may be unreadable after."""

    def _make_legacy(self, path):
        """Fabricate a pre-KV store directly in the legacy on-disk
        format: sealed v3 checkpoint + two metadata-op WAL records."""
        from ceph_tpu.kv.tindb import append_wal_record, host_crc32c
        from ceph_tpu.osd.tinstore import (_encode_meta_txn,
                                           ExtentAllocator)
        from ceph_tpu.utils.encoding import Encoder
        os.makedirs(path, exist_ok=True)
        payloads = {"o1": b"legacy object one", "o2": b"second" * 40}
        doffs = {}
        with open(os.path.join(path, "block.dev"), "wb") as dev:
            off = 0
            for oid, data in payloads.items():
                dev.write(data)
                doffs[oid] = (off, ExtentAllocator.round_up(len(data)))
                pad = doffs[oid][1] - len(data)
                dev.write(b"\x00" * pad)
                off += doffs[oid][1]
        e = Encoder()
        e.start(3, 3)
        e.u64(0)                      # base_seq
        e.u64(1)                      # committed_txns at checkpoint
        e.u32(1)                      # one collection
        e.string("c")
        e.u32(len(payloads))
        from ceph_tpu.osd.tinstore import _crc32c
        for oid, data in payloads.items():
            doff, dlen = doffs[oid]
            e.string(oid)
            e.u64(len(data)).u64(doff).u64(dlen).u32(_crc32c(data))
            e.mapping({"who": b"ckpt"}, Encoder.string, Encoder.blob)
            e.mapping({b"ck": b"from-ckpt"} if oid == "o1" else {},
                      Encoder.blob, Encoder.blob)
            e.string("").u64(0).u32(0)      # uncompressed
        e.finish()
        body = e.bytes()
        body += struct.pack("<I", host_crc32c(body))
        with open(os.path.join(path, "ckpt"), "wb") as f:
            f.write(body)
        with open(os.path.join(path, "wal.log"), "wb") as f:
            append_wal_record(f, 1, _encode_meta_txn(
                [("touch", "c", "o3"),
                 ("omap_set", "c", "o1", {b"wk": b"from-wal"})]),
                o_dsync=False)
            append_wal_record(f, 2, _encode_meta_txn(
                [("setattr", "c", "o3", "hinfo", b"\x07")]),
                o_dsync=False)
        return payloads

    def test_legacy_store_migrates_and_serves(self, tmp_path):
        path = str(tmp_path / "old")
        payloads = self._make_legacy(path)
        # pre-migration fsck sees the legacy format, clean
        rep = TinStore.fsck(path)
        assert rep["format"] == "legacy" and not rep["errors"]
        assert not rep["bad_objects"]
        st = TinStore(path)           # mount = forward migration
        assert os.path.exists(os.path.join(path, "MANIFEST"))
        assert not os.path.exists(os.path.join(path, "ckpt"))
        for oid, data in payloads.items():
            assert bytes(st.read("c", oid)) == data
            assert st.getattr("c", oid, "who") == b"ckpt"
        # checkpoint omap AND wal omap both present, ordered
        assert dict(st.collections["c"]["o1"].omap) \
            == {b"ck": b"from-ckpt", b"wk": b"from-wal"}
        assert st.exists("c", "o3")
        assert st.getattr("c", "o3", "hinfo") == b"\x07"
        # ckpt committed 1 txn + 2 wal records
        assert st.committed_txns == 3
        st.umount()
        rep = TinStore.fsck(path)
        assert rep["format"] == "kv" and not rep["errors"]
        assert not rep["bad_objects"] and not rep["extent_errors"]

    def test_migrated_store_is_durable_and_writable(self, tmp_path):
        path = str(tmp_path / "old")
        payloads = self._make_legacy(path)
        st = TinStore(path)
        st.queue_transaction(
            Transaction().write("c", "post", 0, b"post-migration")
            .omap_set("c", "o3", {b"nk": b"nv"}))
        st.crash()
        st.remount()                  # plain KV remount, no re-migration
        for oid, data in payloads.items():
            assert bytes(st.read("c", oid)) == data
        assert bytes(st.read("c", "post")) == b"post-migration"
        assert dict(st.collections["c"]["o3"].omap) == {b"nk": b"nv"}

    def test_crash_before_manifest_reruns_migration(self, tmp_path):
        # the migration's commit point is the MANIFEST rename: fake
        # the "crashed halfway" window (segment written, no MANIFEST)
        # with a stray orphan segment — remount must re-migrate and
        # reclaim the orphan
        path = str(tmp_path / "old")
        payloads = self._make_legacy(path)
        from ceph_tpu.kv.tindb import write_segment
        write_segment(os.path.join(path, "seg-00000001.tdb"),
                      [(b"O\x00half", b"way")])
        st = TinStore(path)           # _is_legacy: no MANIFEST -> migrate
        for oid, data in payloads.items():
            assert bytes(st.read("c", oid)) == data
        assert st._db.get("O", b"half") is None
        st.umount()
        assert not TinStore.fsck(path)["errors"]

    def test_legacy_mid_log_corruption_still_fatal(self, tmp_path):
        path = str(tmp_path / "old")
        self._make_legacy(path)
        with open(os.path.join(path, "wal.log"), "r+b") as f:
            f.seek(20)
            f.write(b"\xff\xff\xff")
        with pytest.raises(TinStoreCorruption):
            TinStore(path)
        rep = TinStore.fsck(path)
        assert rep["format"] == "legacy" and rep["errors"]


def _random_txn(rng, shadow: MemStore) -> Transaction:
    """One transaction of byte, attr and teardown ops over two
    collections and three objects, several ops an object. `shadow`
    applies each op as it is drawn, so a truncate can aim at the length
    the transaction has left so far (down, up, or the same)."""
    t = Transaction()

    def emit(op):
        t.ops.append(op)
        one = Transaction()
        one.ops.append(op)
        shadow.queue_transaction(one)

    def length(c, o):
        return shadow.stat(c, o) if shadow.exists(c, o) else 0

    def payload():
        return rng.integers(0, 256, int(rng.integers(0, 3000)), np.uint8)

    for _ in range(int(rng.integers(6, 16))):
        c = str(rng.choice(["a", "b"]))
        o = str(rng.choice(["o0", "o1", "o2"]))
        kind = int(rng.integers(0, 9))
        if kind == 0:
            emit(("write", c, o, int(rng.integers(0, 2000)), payload()))
        elif kind == 1:
            emit(("xor", c, o, int(rng.integers(0, 2000)), payload()))
        elif kind == 2:
            n = length(c, o)
            size = int(rng.choice([max(0, n - int(rng.integers(1, 500))),
                                   n + int(rng.integers(1, 500)), n]))
            emit(("truncate", c, o, size))
        elif kind == 3:
            emit(("remove", c, o))
            emit(("write", c, o, int(rng.integers(0, 100)), payload()))
        elif kind == 4:
            emit(("rmcoll", c))
            emit(("mkcoll", c))
        elif kind == 5:
            emit(("setattr", c, o, str(rng.choice(["h", "i"])),
                  rng.bytes(int(rng.integers(1, 9)))))
        elif kind == 6:
            emit(("rmattr", c, o, "h"))
        elif kind == 7:
            emit(("touch", c, o))
        else:                    # a shard write: row, same-length truncate
            row = payload()
            emit(("write", c, o, 0, row))
            emit(("truncate", c, o, len(row)))
            emit(("setattr", c, o, "hinfo", rng.bytes(8)))
    return t


def _assert_same_state(tin: TinStore, mem: MemStore) -> None:
    from ceph_tpu.osd.tinstore import _crc32c
    assert tin.list_collections() == sorted(mem.collections)
    for cid, coll in mem.collections.items():
        assert tin.list_objects(cid) == sorted(coll)
        for oid, o in coll.items():
            assert tin.stat(cid, oid) == len(o.data)
            assert bytes(tin.read(cid, oid)) == o.data.tobytes()
            assert dict(tin.collections[cid][oid].xattrs) == o.xattrs
            if len(o.data):       # an empty object's crc is never read
                assert tin._meta[cid][oid].crc == _crc32c(o.data)
    # every live extent is an object's; nothing staged and let go leaks
    assert tin._alloc.used_bytes() == sum(
        o.dlen for coll in tin._meta.values() for o in coll.values())


class TestTinStoreStagesOnce:
    """A transaction folds each object's byte ops in order and stages
    the object once, on its final bytes (ref: BlueStore writes an
    object's data once per TransContext; _do_truncate returns at once
    when the size is the onode's)."""

    @pytest.mark.parametrize("compression", [None, "zlib"])
    @pytest.mark.parametrize("seed", range(12))
    def test_random_txn_matches_memstore_before_and_after_replay(
            self, tmp_path, seed, compression):
        rng = np.random.default_rng(3900 + seed)
        base = Transaction().create_collection("a").create_collection("b")
        for c in ("a", "b"):
            for o in ("o0", "o1"):
                base.write(c, o, 0, rng.integers(0, 256, 1500, np.uint8))
                base.setattr(c, o, "h", b"base")
        mem, shadow = MemStore(), MemStore()
        tin = TinStore(str(tmp_path / "s"), compression=compression,
                       compression_min_blob=1)
        for st in (mem, shadow, tin):
            st.queue_transaction(base)
        txn = _random_txn(rng, shadow)
        mem.queue_transaction(txn)
        tin.queue_transaction(txn)
        _assert_same_state(tin, mem)
        tin.crash()
        tin.remount()                     # the KV plane's WAL replayed
        _assert_same_state(tin, mem)
        rep = TinStore.fsck(str(tmp_path / "s"))
        assert not rep["bad_objects"] and not rep["errors"]
        assert not rep["extent_errors"]

    def test_a_shard_write_stages_one_extent(self, tmp_path, monkeypatch):
        from ceph_tpu.kv.tindb import kv_perf
        from ceph_tpu.osd import tinstore
        st = TinStore(str(tmp_path / "s"))
        st.queue_transaction(Transaction().create_collection("c"))
        row = np.random.default_rng(1).integers(0, 256, 512 << 10,
                                                np.uint8)
        writes = []
        real = tinstore.os.pwrite
        monkeypatch.setattr(tinstore.os, "pwrite",
                            lambda fd, b, off: writes.append(len(b))
                            or real(fd, b, off))
        before = kv_perf.dump()
        used = st._alloc.used_bytes()
        st.queue_transaction(
            Transaction().write("c", "o", 0, row).truncate("c", "o", len(row))
            .setattr("c", "o", "hinfo", b"\x01"))
        after = kv_perf.dump()
        assert writes == [512 << 10]                # one pwrite, one extent
        assert st._alloc.used_bytes() - used == 512 << 10
        assert after["store_objects_staged"] \
            - before["store_objects_staged"] == 1
        assert after["store_byte_ops_folded"] \
            - before["store_byte_ops_folded"] == 1
        assert bytes(st.read("c", "o")) == row.tobytes()
        # a same-length truncate alone writes nothing at all
        st.queue_transaction(Transaction().truncate("c", "o", len(row)))
        assert writes == [512 << 10]
        assert kv_perf.dump()["store_objects_staged"] \
            == after["store_objects_staged"]
        st.crash()
        st.remount()
        assert bytes(st.read("c", "o")) == row.tobytes()
        assert st.getattr("c", "o", "hinfo") == b"\x01"

    def test_enospc_on_the_second_object_leaves_store_as_before(
            self, tmp_path):
        import errno
        st = TinStore(str(tmp_path / "s"))
        rng = np.random.default_rng(2)
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "old", 0, rng.integers(0, 256, 9000, np.uint8)))
        def kv_rows(store):
            return [(p, k, v) for p in "COMS"
                    for k, v in store._db.iterate(p)]

        kv0 = kv_rows(st)
        used0, txns0 = st._alloc.used_bytes(), st.committed_txns
        # room for one more 512 KiB extent, not for two
        st.set_capacity(st.used_bytes() + (512 << 10) + 2048)
        t = Transaction()
        for name in ("o1", "o2"):
            row = rng.integers(0, 256, 512 << 10, np.uint8)
            t.write("c", name, 0, row).truncate("c", name, len(row)) \
             .setattr("c", name, "hinfo", b"h")
        with pytest.raises(OSError) as e:
            st.queue_transaction(t)
        assert e.value.errno == errno.ENOSPC
        # the first object's extent went back (the device may have grown)
        assert st._alloc.used_bytes() == used0
        assert st.committed_txns == txns0
        assert kv_rows(st) == kv0
        assert st.list_objects("c") == ["old"]
        st.crash()
        st.remount()
        assert kv_rows(st) == kv0 and st.committed_txns == txns0
        assert st._alloc.used_bytes() == used0

    @pytest.mark.parametrize("view", ["contiguous", "strided", "readonly",
                                      "words"])
    def test_host_crc_reads_an_array_in_place(self, view):
        from ceph_tpu.kv import host_crc32c
        a = np.random.default_rng(4).integers(0, 256, 4099, np.uint8)
        arr = {"contiguous": a, "strided": a[::3],
               "readonly": np.frombuffer(a.tobytes(), np.uint8),
               "words": a[:4096].view(np.uint32)}[view]
        assert host_crc32c(arr) == host_crc32c(arr.tobytes())


def test_store_bench_tool_smoke():
    """tools/store_bench.py (the fio_ceph_objectstore role) runs both
    backends and emits sane JSON."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for extra in (["--store", "mem"], ["--store", "tin"]):
        r = subprocess.run(
            [sys.executable, "tools/store_bench.py", "--seconds", "0.5",
             "--objects", "32", "--object-size", "8192", "--json",
             *extra, "randwrite"],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=repo)
        assert r.returncode == 0, r.stderr[-400:]
        d = json.loads(r.stdout.strip().splitlines()[-1])
        assert d["iops"] > 0 and d["mb_per_s"] > 0
        assert d["ops"] >= d["txn_ops"]
