"""RMW partial-stripe write tests (ref: ECCommon::RMWPipeline::start_rmw,
ECTransaction::generate_transactions — arbitrary (offset, len) overwrites
read the touched stripes' pre-image, re-encode, and sub-write shards).

The property test mirrors the reference's thrash-under-io pattern
(qa/tasks/ceph_manager.py Thrasher): random full/partial writes
interleaved with OSD kills and recoveries, every read byte-exact vs a
host-side shadow copy.
"""

import numpy as np
import pytest

from ceph_tpu.osd.ecbackend import ECBackend, ShardSet, shard_cid


def make_backend(profile="plugin=tpu_rs k=4 m=2",
                 n_osds=6, chunk_size=256):
    cluster = ShardSet()
    be = ECBackend(profile, "1.0", list(range(n_osds)), cluster,
                   chunk_size=chunk_size)
    return be, cluster


class TestWriteAt:
    def test_overwrite_within_one_stripe(self):
        be, _ = make_backend()
        rng = np.random.default_rng(0)
        base = rng.integers(0, 256, size=3000, dtype=np.uint8)
        be.write_objects({"o": base})
        patch = rng.integers(0, 256, size=100, dtype=np.uint8)
        be.write_at("o", 50, patch)
        want = base.copy()
        want[50:150] = patch
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []

    def test_overwrite_spanning_stripes(self):
        be, _ = make_backend()
        rng = np.random.default_rng(1)
        sw = be.sinfo.stripe_width
        base = rng.integers(0, 256, size=sw * 3 + 17, dtype=np.uint8)
        be.write_objects({"o": base})
        patch = rng.integers(0, 256, size=sw + 33, dtype=np.uint8)
        off = sw - 5
        be.write_at("o", off, patch)
        want = base.copy()
        want[off:off + len(patch)] = patch
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []

    def test_extending_write(self):
        be, _ = make_backend()
        rng = np.random.default_rng(2)
        base = rng.integers(0, 256, size=500, dtype=np.uint8)
        be.write_objects({"o": base})
        tail = rng.integers(0, 256, size=800, dtype=np.uint8)
        be.write_at("o", 450, tail)
        want = np.concatenate([base[:450], tail])
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []

    def test_write_past_end_zero_gap(self):
        be, _ = make_backend()
        rng = np.random.default_rng(3)
        base = rng.integers(0, 256, size=100, dtype=np.uint8)
        be.write_objects({"o": base})
        sw = be.sinfo.stripe_width
        patch = rng.integers(0, 256, size=64, dtype=np.uint8)
        off = sw * 2 + 7  # leaves a hole of untouched stripes
        be.write_at("o", off, patch)
        want = np.zeros(off + 64, dtype=np.uint8)
        want[:100] = base
        want[off:off + 64] = patch
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []

    def test_write_at_creates_object(self):
        be, _ = make_backend()
        rng = np.random.default_rng(4)
        patch = rng.integers(0, 256, size=300, dtype=np.uint8)
        be.write_at("new", 40, patch)
        want = np.zeros(340, dtype=np.uint8)
        want[40:] = patch
        np.testing.assert_array_equal(be.read_object("new"), want)

    def test_empty_write_noop_and_creation(self):
        be, _ = make_backend()
        be.write_at("e", 0, b"")
        assert be.read_object("e").size == 0
        rng = np.random.default_rng(5)
        base = rng.integers(0, 256, size=100, dtype=np.uint8)
        be.write_objects({"o": base})
        be.write_at("o", 10, b"")
        np.testing.assert_array_equal(be.read_object("o"), base)

    def test_batched_write_ranges_multiple_objects(self):
        be, _ = make_backend()
        rng = np.random.default_rng(6)
        objs = {f"o{i}": rng.integers(0, 256, size=2048, dtype=np.uint8)
                for i in range(5)}
        be.write_objects(dict(objs))
        ops = []
        for i, name in enumerate(objs):
            patch = rng.integers(0, 256, size=64, dtype=np.uint8)
            ops.append((name, 100 + 17 * i, patch))
            objs[name][100 + 17 * i:100 + 17 * i + 64] = patch
        be.write_ranges(ops)
        got = be.read_objects(list(objs))
        for name, want in objs.items():
            np.testing.assert_array_equal(got[name], want, err_msg=name)
        assert be.deep_scrub()["inconsistent"] == []

    def test_multiple_ranges_same_object_merge(self):
        be, _ = make_backend()
        rng = np.random.default_rng(7)
        base = rng.integers(0, 256, size=4000, dtype=np.uint8)
        be.write_objects({"o": base})
        a = rng.integers(0, 256, size=50, dtype=np.uint8)
        b = rng.integers(0, 256, size=60, dtype=np.uint8)
        be.write_ranges([("o", 10, a), ("o", 3000, b)])
        want = base.copy()
        want[10:60] = a
        want[3000:3060] = b
        np.testing.assert_array_equal(be.read_object("o"), want)


class TestDegradedRMW:
    def test_rmw_with_down_data_shard(self):
        """Write with a data shard's OSD down: pre-image reconstructed
        from survivors, parity stays consistent, recovery rebuilds the
        down shard with the NEW bytes."""
        be, cluster = make_backend()
        rng = np.random.default_rng(10)
        base = rng.integers(0, 256, size=3000, dtype=np.uint8)
        be.write_objects({"o": base})
        dead_osd = be.acting[1]  # data shard slot 1
        cluster.stores.pop(dead_osd)
        patch = rng.integers(0, 256, size=500, dtype=np.uint8)
        be.write_at("o", 200, patch, dead_osds={dead_osd})
        want = base.copy()
        want[200:700] = patch
        np.testing.assert_array_equal(
            be.read_object("o", dead_osds={dead_osd}), want)
        # recovery rebuilds slot 1 from the new stripe content
        be.recover_shards([1], replacement_osds={1: 77})
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []

    def test_rmw_with_down_parity_shard(self):
        be, cluster = make_backend()
        rng = np.random.default_rng(11)
        base = rng.integers(0, 256, size=3000, dtype=np.uint8)
        be.write_objects({"o": base})
        dead_osd = be.acting[be.k]  # first parity slot
        cluster.stores.pop(dead_osd)
        patch = rng.integers(0, 256, size=100, dtype=np.uint8)
        be.write_at("o", 700, patch, dead_osds={dead_osd})
        want = base.copy()
        want[700:800] = patch
        be.recover_shards([be.k], replacement_osds={be.k: 78})
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []


class TestClayRMW:
    def test_clay_falls_back_to_whole_object(self):
        be, _ = make_backend(profile="plugin=clay k=4 m=2 d=5 impl=ref",
                             chunk_size=None)
        rng = np.random.default_rng(12)
        base = rng.integers(0, 256, size=5000, dtype=np.uint8)
        be.write_objects({"o": base})
        patch = rng.integers(0, 256, size=70, dtype=np.uint8)
        be.write_at("o", 123, patch)
        want = base.copy()
        want[123:193] = patch
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []


class TestRMWProperty:
    @pytest.mark.parametrize("profile", [
        "plugin=tpu_rs k=4 m=2",
        "plugin=tpu_rs k=3 m=3 technique=cauchy_good",
    ])
    def test_thrash_partial_writes_and_kills(self, profile):
        """Random full/partial writes interleaved with OSD kills and
        recoveries; every read byte-exact vs the host shadow."""
        rng = np.random.default_rng(99)
        be, cluster = make_backend(profile=profile, n_osds=6, chunk_size=256)
        shadow: dict[str, np.ndarray] = {}
        dead: dict[int, int] = {}  # slot -> dead osd id
        next_osd = 100
        for step in range(60):
            op = rng.choice(["full", "partial", "kill", "recover", "verify"],
                            p=[0.2, 0.45, 0.1, 0.1, 0.15])
            dead_osds = set(dead.values())
            if op == "full":
                name = f"obj{rng.integers(0, 8)}"
                size = int(rng.integers(0, 3000))
                data = rng.integers(0, 256, size=size, dtype=np.uint8)
                # full-object rewrite must work degraded too: route via
                # write_ranges when shards are down (write_objects is the
                # clean-path batch API)
                if dead_osds:
                    be.write_ranges([(name, 0, data)], dead_osds=dead_osds)
                    if name in shadow and len(shadow[name]) > size:
                        # emulate truncate-to-size of a full rewrite:
                        # write_ranges alone extends, so pad the shadow
                        grown = shadow[name].copy()
                        grown[:size] = data
                        shadow[name] = grown
                    else:
                        shadow[name] = data
                else:
                    be.write_objects({name: data})
                    shadow[name] = data
            elif op == "partial":
                name = f"obj{rng.integers(0, 8)}"
                old = shadow.get(name, np.zeros(0, dtype=np.uint8))
                off = int(rng.integers(0, 2500))
                ln = int(rng.integers(1, 600))
                patch = rng.integers(0, 256, size=ln, dtype=np.uint8)
                be.write_at(name, off, patch, dead_osds=dead_osds)
                new_len = max(len(old), off + ln)
                grown = np.zeros(new_len, dtype=np.uint8)
                grown[:len(old)] = old
                grown[off:off + ln] = patch
                shadow[name] = grown
            elif op == "kill" and len(dead) < be.m:
                alive = [s for s in range(be.n) if s not in dead]
                slot = int(rng.choice(alive))
                dead[slot] = be.acting[slot]
                cluster.stores.pop(be.acting[slot], None)
            elif op == "recover" and dead:
                slots = sorted(dead)
                be.recover_shards(slots, replacement_osds={
                    s: next_osd + i for i, s in enumerate(slots)})
                next_osd += len(slots)
                dead.clear()
            else:  # verify
                if shadow:
                    got = be.read_objects(list(shadow),
                                          dead_osds=set(dead.values()))
                    for name, want in shadow.items():
                        np.testing.assert_array_equal(
                            got[name], want, err_msg=f"step {step} {name}")
        # final: recover everything and verify clean
        if dead:
            slots = sorted(dead)
            be.recover_shards(slots, replacement_osds={
                s: next_osd + i for i, s in enumerate(slots)})
        got = be.read_objects(list(shadow))
        for name, want in shadow.items():
            np.testing.assert_array_equal(got[name], want, err_msg=name)
        assert be.deep_scrub()["inconsistent"] == []


class TestClayDegradedExtendingRMW:
    def test_clay_degraded_extend_preserves_old_bytes(self):
        """Review regression: clay sub-chunk geometry depends on chunk
        length, so the degraded pre-image must be decoded at the OLD
        shard length, not the zero-extended new one."""
        be, cluster = make_backend(profile="plugin=clay k=4 m=2 d=5 impl=ref",
                                   chunk_size=None)
        rng = np.random.default_rng(21)
        sw = be.sinfo.stripe_width
        base = rng.integers(0, 256, size=sw, dtype=np.uint8)
        be.write_objects({"o": base})
        dead_osd = be.acting[1]
        cluster.stores.pop(dead_osd)
        patch = rng.integers(0, 256, size=300, dtype=np.uint8)
        be.write_at("o", sw, patch, dead_osds={dead_osd})  # extends
        want = np.concatenate([base, patch])
        np.testing.assert_array_equal(
            be.read_object("o", dead_osds={dead_osd}), want)
        # the destroyed OSD id must NOT have been resurrected
        assert dead_osd not in cluster.stores
        be.recover_shards([1], replacement_osds={1: 55})
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.deep_scrub()["inconsistent"] == []
