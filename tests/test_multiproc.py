"""Multi-process OSD cells (r13): every OSD daemon a real OS process
(SIGKILL = the process vanishes), monitors/clients in the test
process, control via stdin pipes + admin sockets.

Budget shape: the kill/revive thrash cells are slow-marked (child
spawns cost seconds each on this 1-CPU box) with their deadlines
scaled by chaos.load_factor(); tier-1 keeps one cheap boot+IO smoke
here plus the single-process 2-shard thrash representative in
test_thrash.py."""

import os
import threading
import time

import pytest

from ceph_tpu.chaos.thrasher import load_factor
from ceph_tpu.osd.standalone import StandaloneCluster

LF = load_factor()


def _proc_cluster(tmp_path, n_osds, store="tin", op_shards=2):
    return StandaloneCluster(
        n_osds=n_osds, pg_num=2, store=store,
        store_dir=str(tmp_path / "osds") if store == "tin" else None,
        osd_procs=True, op_shards=op_shards,
        cephx=True, secret=os.urandom(32),
        profile="plugin=tpu_rs k=2 m=1",
        # deadline scaling, not schedule input: a loaded host
        # stretches child spawn + every ping round trip
        hb_grace=1.2 * LF)


def test_multiproc_boot_rw_smoke(tmp_path):
    """Tier-1 representative: children spawn, fold the map, serve
    bit-exact IO under cephx+secure, and answer their admin sockets
    (the observability side channel the proc harness runs on)."""
    c = _proc_cluster(tmp_path, n_osds=3)
    try:
        c.wait_for_clean(timeout=40 * LF)
        cl = c.client()
        objs = {f"mp-{i}": bytes([i]) * 2048 for i in range(8)}
        cl.write(objs)
        for n, v in objs.items():
            assert bytes(cl.read(n)) == v, n
        # the admin-socket plane: declared counters + shard occupancy
        h = next(iter(c.osds.values()))
        dump = h.asok("perf dump")
        assert "msgr" in dump and dump["msgr"]["frames_rx"] > 0
        shards = h.asok("dump_op_shards")
        assert set(shards) == {"shard_0", "shard_1"}
        # r15 control parity: key rotation pushes cross the child
        # control pipe (stdin, never argv) — IO keeps flowing through
        # the keep-window, and a SECOND rotation still serves (the
        # refreshed verifier accepted tickets minted pre-rotation)
        c.rotate_service_secrets("osd")
        cl.write({"mp-rot": b"R" * 1024})
        c.rotate_service_secrets("osd")
        assert bytes(cl.read("mp-rot")) == b"R" * 1024
        # store-fsck control line: a quiesced online audit inside the
        # child answers on stdout — TinStore children run the real
        # offline audit over their mounted directory
        rep = h.store_fsck()
        assert rep["errors"] == [] and rep["bad_objects"] == []
        assert rep.get("format", "kv") in ("kv", "legacy")
    finally:
        c.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_multiproc_thrash_smoke_kill_mid_window(tmp_path):
    """The r13 acceptance cell: SIGKILL an OSD process MID write
    window; every ACKED write must read back bit-exact after heal
    (exactly-once), acked removes stay dead after the revive
    remounts the victim's store (no resurrection)."""
    c = _proc_cluster(tmp_path, n_osds=4)
    try:
        c.wait_for_clean(timeout=60 * LF)
        cl = c.client()
        base = {f"g1-{i}": bytes([i]) * 4096 for i in range(10)}
        cl.write(base)
        shadow = dict(base)
        errors = []
        torn: set[str] = set()

        def writer():
            for i in range(24):
                name = f"g2-{i % 8}"
                val = bytes([100 + i]) * 4096
                try:
                    cl.write({name: val})
                    shadow[name] = val       # acked: must persist
                except Exception as e:   # noqa: BLE001 — op raced
                    torn.add(name)       # the kill: either value ok
                    errors.append(str(e))
                time.sleep(0.05)
        t = threading.Thread(target=writer)
        t.start()
        time.sleep(0.3)                      # mid-window
        victim = max(c.osd_ids())
        c.kill_osd(victim)
        t.join(60 * LF)
        assert not t.is_alive()
        c.wait_for_down(victim, timeout=40 * LF)
        c.wait_for_clean(timeout=90 * LF)
        for n, v in shadow.items():
            if n in torn:
                continue                     # unacked proves nothing
            assert bytes(cl.read(n)) == v, n
        # acked removes survive the victim's WAL remount
        dead = sorted(base)[:3]
        cl.remove(dead)
        for n in dead:
            shadow.pop(n)
        c.revive_osd(victim)
        c.wait_for_clean(timeout=90 * LF)
        for n, v in shadow.items():
            if n in torn:
                continue
            assert bytes(cl.read(n)) == v, n
        for n in dead:
            with pytest.raises((KeyError, RuntimeError,
                                ConnectionError)):
                cl.read(n)
    finally:
        c.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_multiproc_memstore_kill_rebuilds_from_survivors(tmp_path):
    """A MemStore child loses EVERYTHING at SIGKILL (RAM is RAM):
    after the down-mark the survivors must rebuild the lost shards
    and serve every acked byte — the decode-rebuild path across
    process boundaries."""
    c = _proc_cluster(tmp_path, n_osds=4, store="mem")
    try:
        c.wait_for_clean(timeout=60 * LF)
        cl = c.client()
        objs = {f"m-{i}": os.urandom(4096) for i in range(12)}
        cl.write(objs)
        victim = max(c.osd_ids())
        c.kill_osd(victim)
        c.wait_for_down(victim, timeout=40 * LF)
        c.wait_for_clean(timeout=90 * LF)
        for n, v in objs.items():
            assert bytes(cl.read(n)) == v, n
    finally:
        c.shutdown()


def test_multiproc_refuses_a_parent_on_an_accelerator(monkeypatch):
    """One chip belongs to one process: under a non-CPU parent the OSD
    children could only serve erasure coding from the host, so the
    spawn raises instead of falling back quietly."""
    import jax

    from ceph_tpu.osd.multiproc import OSDProcHandle
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="osd_procs=True needs a CPU"):
        OSDProcHandle(cluster=None, osd_id=0)
