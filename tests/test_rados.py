"""librados-shaped API + striper + remove semantics (refs:
src/librados/librados.cc C API, src/libradosstriper/RadosStriperImpl.cc,
pg_log DELETE replay)."""

import numpy as np
import pytest

from ceph_tpu.client.rados import Rados, RadosStriper
from cluster_helpers import corpus, make_cluster


def make_io(**kw):
    c = make_cluster(**kw)
    return c, Rados(c).open_ioctx()


class TestIoCtx:
    def test_write_read_stat_remove(self):
        c, io = make_io()
        io.write_full("obj", b"hello world")
        assert io.read("obj") == b"hello world"
        assert io.read("obj", length=5) == b"hello"
        assert io.read("obj", length=5, offset=6) == b"world"
        assert io.stat("obj") == 11
        io.write("obj", b"WALD", offset=6)
        assert io.read("obj") == b"hello WALDd"
        io.remove("obj")
        with pytest.raises(KeyError):
            io.read("obj")
        with pytest.raises(KeyError):
            io.remove("obj")

    def test_list_objects(self):
        c, io = make_io(pg_num=4)
        for i in range(6):
            io.write_full(f"o{i}", bytes([i]))
        assert io.list_objects() == [f"o{i}" for i in range(6)]
        io.remove("o3")
        assert "o3" not in io.list_objects()

    def test_bad_pool(self):
        c, _ = make_io()
        with pytest.raises(ValueError):
            Rados(c).open_ioctx("nope")


class TestRemoveReplay:
    def test_missed_delete_replays_on_rejoin(self):
        c, io = make_io(pg_num=4, down_out_interval=10_000)
        objs = corpus(8, 300, seed=1)
        for n, d in objs.items():
            io.write_full(n, d.tobytes())
        victim = c.pgs[c.locate(next(iter(objs)))].acting[1]
        c.kill_osd(victim)
        c.tick(30)
        doomed = [n for n in objs
                  if victim in c.pgs[c.locate(n)].acting][:2]
        assert doomed, "victim should host some objects"
        for n in doomed:
            io.remove(n)
        c.revive_osd(victim)
        # the revived shard must not hold a stale copy of the removed
        # objects (delete replayed), and scrub must be clean
        from ceph_tpu.osd.ecbackend import shard_cid
        for n in doomed:
            ps = c.locate(n)
            be = c.pgs[ps]
            for slot, osd in enumerate(be.acting):
                st = c.cluster.osd(osd)
                assert not st.exists(shard_cid(be.pg, slot), n), (n, slot)
            rep = be.shallow_scrub()
            assert rep["errors"] == [], rep

    def test_remove_then_backfill_does_not_resurrect(self):
        c, io = make_io(pg_num=4, down_out_interval=60.0)
        objs = corpus(12, 300, seed=2)
        for n, d in objs.items():
            io.write_full(n, d.tobytes())
        c.backfill_rate = 2
        c.kill_osd(0)
        c.tick(30)
        c.tick(60)
        c.revive_osd(0)  # mark-in -> backfill moves start
        # remove objects mid-backfill
        removed = list(objs)[:4]
        for n in removed:
            io.remove(n)
        for _ in range(120):
            if not c.backfills:
                break
            c.tick(6)
        assert not c.backfills
        live = [n for n in objs if n not in removed]
        for n in live:
            assert io.read(n) == objs[n].tobytes()
        for n in removed:
            with pytest.raises(KeyError):
                io.read(n)
        for be in c.pgs.values():
            assert be.shallow_scrub()["errors"] == []


class TestStriper:
    def test_roundtrip_and_layout(self):
        c, io = make_io(pg_num=4)
        st = RadosStriper(io, stripe_unit=64, stripe_count=3,
                          object_size=256)
        rng = np.random.default_rng(3)
        blob = rng.integers(0, 256, 2000, np.uint8).tobytes()
        st.write("vol", blob)
        assert st.size("vol") == 2000
        assert st.read("vol") == blob
        # adjacent stripe units land on different objects
        names = [n for n in io.list_objects() if n.startswith("vol.")
                 and not n.endswith(".meta")]
        assert len(names) > 3
        # partial reads at arbitrary offsets
        for off, ln in ((0, 64), (63, 2), (100, 700), (1990, 50)):
            assert st.read("vol", length=ln, offset=off) == \
                blob[off:off + ln]

    def test_overwrite_and_extend(self):
        c, io = make_io(pg_num=2)
        st = RadosStriper(io, stripe_unit=32, stripe_count=2,
                          object_size=64)
        st.write("v", b"A" * 100)
        st.write("v", b"B" * 40, offset=30)
        want = b"A" * 30 + b"B" * 40 + b"A" * 30
        assert st.read("v") == want
        st.write("v", b"C" * 10, offset=95)   # extends to 105
        assert st.size("v") == 105
        assert st.read("v")[95:] == b"C" * 10

    def test_remove_cleans_objects(self):
        c, io = make_io(pg_num=2)
        st = RadosStriper(io, stripe_unit=32, stripe_count=2,
                          object_size=64)
        st.write("gone", b"x" * 500)
        assert any(n.startswith("gone.") for n in io.list_objects())
        st.remove("gone")
        assert not any(n.startswith("gone.") for n in io.list_objects())
        with pytest.raises(KeyError):
            st.size("gone")

    def test_survives_osd_loss(self):
        c, io = make_io(pg_num=4, down_out_interval=60.0)
        st = RadosStriper(io, stripe_unit=128, stripe_count=4,
                          object_size=512)
        rng = np.random.default_rng(4)
        blob = rng.integers(0, 256, 5000, np.uint8).tobytes()
        st.write("data", blob)
        c.kill_osd(1)
        c.tick(30)
        c.tick(90)
        for _ in range(60):
            if not c.backfills:
                break
            c.tick(6)
        assert st.read("data") == blob


def test_log_trimmed_rejoin_purges_deleted_objects():
    # regression: delete + log trim while a shard is down; the BACKFILL
    # rejoin must purge the deleted object from the shard's old store
    c, io = make_io(pg_num=2, down_out_interval=10_000)
    io.write_full("doomed", b"z" * 300)
    ps = c.locate("doomed")
    be = c.pgs[ps]
    be.pg_log.max_entries = 4
    victim = be.acting[1]
    c.kill_osd(victim)
    c.tick(30)
    io.remove("doomed")
    fill = next(n for n in (f"fill{i}" for i in range(64))
                if c.locate(n) == ps)
    for r in range(6):  # push the delete past the log tail
        io.write_full(fill, bytes([r]) * 100)
    assert be.pg_log.missing_since(be.shard_applied[1]) is None
    c.revive_osd(victim)
    from ceph_tpu.osd.ecbackend import shard_cid
    st = c.cluster.osd(victim)
    assert not st.exists(shard_cid(be.pg, 1), "doomed")
    assert be.shallow_scrub()["errors"] == []


class TestStriperConcurrency:
    def test_concurrent_writers_keep_size(self):
        """The size/hwm metadata update is a read-modify-write; two
        aio-pool threads extending one striped object must not lose a
        size extension (r4 advisor finding — serialized per-soid)."""
        import threading
        c, io = make_io(pg_num=2)
        st = RadosStriper(io, stripe_unit=32, stripe_count=2,
                          object_size=64)
        n_threads, per = 8, 256
        barrier = threading.Barrier(n_threads)

        def writer(i):
            barrier.wait()
            st.write("shared", bytes([i]) * per, offset=i * per)

        ts = [threading.Thread(target=writer, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert st.size("shared") == n_threads * per
        got = st.read("shared")
        for i in range(n_threads):
            assert got[i * per:(i + 1) * per] == bytes([i]) * per


def test_rados_cli_roundtrip(tmp_path):
    """tools/rados_cli.py (the `rados` object CLI role): put/ls/stat/
    get/rm compose across invocations, bytes exact."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    state = str(tmp_path / "st")
    payload = bytes(range(256)) * 20
    src = tmp_path / "in.bin"
    src.write_bytes(payload)
    run = lambda *args: subprocess.run(
        [sys.executable, "tools/rados_cli.py", "--state", state, *args],
        capture_output=True, timeout=180, env=env, cwd=repo)
    assert run("put", "o1", str(src)).returncode == 0
    out = run("ls")
    assert out.returncode == 0 and out.stdout.strip() == b"o1"
    got = run("get", "o1", "-")
    assert got.returncode == 0 and got.stdout == payload
    assert run("rm", "o1").returncode == 0
    missing = run("get", "o1", "-")
    assert missing.returncode != 0
    assert b"no such object" in missing.stderr
