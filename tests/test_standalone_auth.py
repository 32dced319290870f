"""cephx over the wire tier: monitor-issued tickets, OSD session
authorization, caps enforcement, secret rotation (refs:
src/auth/cephx/CephxProtocol.cc, src/mon/AuthMonitor.cc,
OSD::ms_verify_authorizer, OSDCap::is_capable)."""

import numpy as np
import pytest

from ceph_tpu.auth import AuthError
from ceph_tpu.osd.standalone import StandaloneCluster


@pytest.fixture
def cluster():
    c = StandaloneCluster(n_osds=3, pg_num=2, op_timeout=3.0,
                          cephx=True)
    try:
        c.wait_for_clean(timeout=20)
        yield c
    finally:
        c.shutdown()


def corpus(seed, n=6):
    rng = np.random.default_rng(seed)
    return {f"authobj-{seed}-{i}":
            rng.integers(0, 256, 300, np.uint8).tobytes()
            for i in range(n)}


class TestCephxWire:
    def test_admin_io_authenticates_transparently(self, cluster):
        """First op hits EPERM:unauthenticated, the client runs the
        full ticket dance over MAuthOp frames, the op retries and
        succeeds — and the data is bytes-exact."""
        cl = cluster.client()
        objs = corpus(1)
        cl.write(objs)
        for name, want in objs.items():
            assert cl.read(name) == want
        # sessions actually exist on the daemons
        assert any(d._authed for d in cluster.osds.values())

    def test_wrong_secret_cannot_login(self, cluster):
        cl = cluster.client(secret=b"\x00" * 32)
        with pytest.raises(AuthError, match="bad proof"):
            cl.write(corpus(2))

    def test_unknown_entity_rejected(self, cluster):
        cl = cluster.client(entity="client.ghost",
                            secret=b"\x01" * 32)
        with pytest.raises(AuthError, match="unknown entity"):
            cl.write(corpus(3))

    def test_readonly_caps_enforced(self, cluster):
        admin = cluster.client()
        objs = corpus(4)
        admin.write(objs)
        ro_secret = cluster.create_entity(
            "client.reader", caps={"mon": "allow r",
                                   "osd": "allow r"})
        ro = cluster.client(entity="client.reader", secret=ro_secret)
        name = next(iter(objs))
        assert ro.read(name) == objs[name]
        with pytest.raises(PermissionError, match="denied need w"):
            ro.write({name: b"overwrite attempt"})
        # the object is untouched
        assert admin.read(name) == objs[name]

    def test_revived_osd_requires_reauth_and_serves(self, cluster):
        """Auth sessions die with the daemon process; after revive the
        client transparently re-authorizes and I/O still works."""
        cl = cluster.client()
        objs = corpus(5)
        cl.write(objs)
        victim = cluster.osd_ids()[0]
        cluster.kill_osd(victim)
        cluster.revive_osd(victim)
        assert cluster.osds[victim]._authed == {}
        more = corpus(6)
        cl.write(more)
        for name, want in {**objs, **more}.items():
            assert cl.read(name) == want

    def test_pool_scoped_caps_match_the_pool(self, cluster):
        """`allow rw pool=default` works against the tier's pool;
        `allow rw pool=other` does not."""
        admin = cluster.client()
        objs = corpus(8)
        admin.write(objs)
        ok_secret = cluster.create_entity(
            "client.pooled", caps={"mon": "allow r",
                                   "osd": "allow rw pool=default"})
        pooled = cluster.client(entity="client.pooled",
                                secret=ok_secret)
        name = next(iter(objs))
        assert pooled.read(name) == objs[name]
        bad_secret = cluster.create_entity(
            "client.wrongpool", caps={"mon": "allow r",
                                      "osd": "allow rw pool=other"})
        wrong = cluster.client(entity="client.wrongpool",
                               secret=bad_secret)
        with pytest.raises(PermissionError):
            wrong.read(name)

    def test_mon_admin_plane_gated(self, cluster):
        """Pool snapshots need a mon ticket with w: the read-only
        entity's mksnap broadcast is dropped (commit-wait times out);
        the admin's goes through."""
        admin = cluster.client()
        admin.write(corpus(9))
        ro_secret = cluster.create_entity(
            "client.monro", caps={"mon": "allow r",
                                  "osd": "allow r"})
        ro = cluster.client(entity="client.monro", secret=ro_secret)
        with pytest.raises(TimeoutError):
            ro.snap_create("sneaky", timeout=2.0)
        sid = admin.snap_create("legit")
        assert sid >= 1

    def test_store_plane_rejects_unauthenticated_frames(self, cluster):
        """Raw MStoreOp frames from a peer with no session bounce with
        EPERM — the data plane can't be reached around the op gate."""
        from ceph_tpu.osd.standalone import (MStoreReply, RemoteStore,
                                             _Rpc)
        admin = cluster.client()
        admin.write(corpus(10))
        # a FRESH endpoint that has never authorized anything: its
        # raw store frames must bounce (sessions are per-peer; the
        # admin's session must not bleed onto this messenger)
        cl = cluster.client()
        target = f"osd.{cluster.osd_ids()[0]}"
        rs = RemoteStore(_Rpc(cl.msgr, MStoreReply.type_id), target,
                         timeout=3.0)  # no authorize callback
        import re
        with pytest.raises(ConnectionError,
                           match=re.escape("EPERM:unauthenticated")):
            rs.list_objects("meta")

    @pytest.mark.slow   # ~22 s thrash cell; nightly (r10 cap fix)
    def test_auth_survives_thrash_rotation_and_partition(self):
        """cephx under chaos: OSD kill/revive, repeated secret
        rotation, and a monitor partition — client I/O keeps flowing
        through transparent re-auth, and every byte survives."""
        import numpy as np
        c = StandaloneCluster(n_osds=4, pg_num=2, op_timeout=3.0,
                              cephx=True)
        try:
            c.wait_for_clean(timeout=20)
            cl = c.client()
            rng = np.random.default_rng(42)
            data: dict[str, bytes] = {}
            for rnd in range(3):
                objs = {f"chaos-{rnd}-{i}":
                        rng.integers(0, 256, 256, np.uint8).tobytes()
                        for i in range(4)}
                cl.write(objs)
                data.update(objs)
                victim = rnd % 4
                c.kill_osd(victim)           # sessions at victim die
                c.rotate_service_secrets("osd")
                if rnd == 1:
                    # a partitioned minority monitor must not break
                    # the auth plane (clients hunt the majority side)
                    c.partition({"mon.2"}, {"mon.0", "mon.1"})
                # write once the quorum has marked the death (the
                # established tier pattern: availability DURING
                # detection is its own suite; this test is about auth
                # riding failure + rotation + partition)
                c._wait(lambda: any(
                    not m._stop.is_set() and m.osdmap is not None
                    and not m.osdmap.osd_up[victim]
                    for m in c.mons), 25, f"osd.{victim} marked down")
                more = {f"chaos-{rnd}-deg-{i}":
                        rng.integers(0, 256, 256, np.uint8).tobytes()
                        for i in range(2)}
                cl.write(more)               # degraded + rotated
                data.update(more)
                if rnd == 1:
                    c.heal_partition()
                c.revive_osd(victim)         # fresh verifier, no
                #                              sessions: forces re-auth
                # recover before the next injection (the qa thrasher's
                # wait_for_clean between disruptions): with k=2 m=1 a
                # second loss during recovery would legitimately drop
                # below min_size — that's durability math, not auth
                c.wait_for_clean(timeout=40)
            for k, want in data.items():
                assert cl.read(k) == want
            # a brand-new client after 3 rotations: the boot-era
            # tickets are long rotated out; the full login + fetch
            # chain must still converge
            cl2 = c.client()
            probe = next(iter(data))
            assert cl2.read(probe) == data[probe]
        finally:
            c.shutdown()

    def test_cephx_on_tinstore_survives_sigkill(self, tmp_path):
        """Cross-feature: ticket auth over a PERSISTENT store — a
        SIGKILLed+revived OSD remounts from WAL, re-fetches rotating
        secrets, and serves the same bytes to re-authenticated
        clients."""
        import numpy as np
        c = StandaloneCluster(n_osds=3, pg_num=2, op_timeout=3.0,
                              cephx=True, store="tin",
                              store_dir=str(tmp_path))
        try:
            c.wait_for_clean(timeout=20)
            cl = c.client()
            rng = np.random.default_rng(7)
            objs = {f"tin-{i}":
                    rng.integers(0, 256, 400, np.uint8).tobytes()
                    for i in range(8)}
            cl.write(objs)
            victim = c.osd_ids()[0]
            c.kill_osd(victim)       # REAL process death: RAM dropped
            c.revive_osd(victim)     # WAL remount + fresh verifier
            c.wait_for_clean(timeout=40)
            for name, want in objs.items():
                assert cl.read(name) == want
        finally:
            c.shutdown()

    def test_thrash_with_injection_knobs_cephx_secure(self, tmp_path):
        """The full-composition chaos cell: ms_inject_socket_failures
        + ms_inject_delay live on every OSD, cephx tickets AND secure
        (encrypted) wire mode on, persistent TinStore under the
        daemons — kill/revive thrash must keep every byte through
        reconnect+replay, re-auth, and WAL remount all at once."""
        import numpy as np
        c = StandaloneCluster(n_osds=4, pg_num=2, op_timeout=6.0,
                              cephx=True, secret=b"\x42" * 32,
                              store="tin", store_dir=str(tmp_path))
        try:
            c.wait_for_clean(timeout=25)
            # every Nth send tears the socket down; every Mth send
            # sleeps — the r5 injection knobs, now composed with the
            # auth + secure + persistence planes instead of isolated
            c.inject_socket_failures(9)
            c.inject_delays(6, 8.0)
            cl = c.client()
            rng = np.random.default_rng(11)
            data: dict[str, bytes] = {}
            for rnd in range(2):
                objs = {f"inj-{rnd}-{i}":
                        rng.integers(0, 256, 300, np.uint8).tobytes()
                        for i in range(4)}
                cl.write(objs)
                data.update(objs)
                victim = c.osd_ids()[rnd % 4]
                c.kill_osd(victim)
                c._wait(lambda: any(
                    not m._stop.is_set() and m.osdmap is not None
                    and not m.osdmap.osd_up[victim]
                    for m in c.mons), 25, f"osd.{victim} marked down")
                more = {f"inj-{rnd}-deg-{i}":
                        rng.integers(0, 256, 300, np.uint8).tobytes()
                        for i in range(2)}
                cl.write(more)           # degraded, through injection
                data.update(more)
                c.revive_osd(victim)     # WAL remount + re-auth; the
                c.inject_socket_failures(9, osds=[victim])  # revived
                c.inject_delays(6, 8.0, osds=[victim])      # daemon
                #                          rejoins the injection matrix
                c.wait_for_clean(timeout=50)
            for name, want in sorted(data.items()):
                assert cl.read(name) == want
        finally:
            c.inject_socket_failures(0)
            c.inject_delays(0, 0.0)
            c.shutdown()

    def test_rotation_keep_window_then_refresh(self, cluster):
        cl = cluster.client()
        objs = corpus(7)
        cl.write(objs)                       # sessions established
        # rotate within the keep-window: existing tickets stay valid
        cluster.rotate_service_secrets("osd")
        name = next(iter(objs))
        assert cl.read(name) == objs[name]
        # rotate past the window: daemons refuse old tickets; a fresh
        # client (new sessions forced) must transparently re-fetch
        cluster.rotate_service_secrets("osd")
        cluster.rotate_service_secrets("osd")
        cl2 = cluster.client()
        assert cl2.read(name) == objs[name]
        cl2.write({name: b"post-rotation write"})
        assert cl.read(name) == b"post-rotation write"


class TestAdminSocketCaps:
    def test_admin_commands_respect_caps(self, cluster):
        """Daemon admin commands ride the same caps gate as reads:
        a reader entity may `perf dump`; a mon-only entity (no osd
        caps) is refused with the _op PermissionError contract."""
        admin = cluster.client()
        admin.write(corpus(9, n=3))
        osd = cluster.osd_ids()[0]
        ro_secret = cluster.create_entity(
            "client.obsv", caps={"mon": "allow r", "osd": "allow r"})
        ro = cluster.client(entity="client.obsv", secret=ro_secret)
        perf = ro.daemon(osd, "perf dump")
        assert f"osd.{osd}" in perf
        no_osd_secret = cluster.create_entity(
            "client.monly", caps={"mon": "allow r"})
        blocked = cluster.client(entity="client.monly",
                                 secret=no_osd_secret)
        with pytest.raises(PermissionError):
            blocked.daemon(osd, "perf dump")


class TestMetaGatherSuspicion:
    """A primary's metadata gather suspects a peer on the peer's silence
    alone: a probe not sent for this daemon's own cold osd ticket says
    nothing of the peer, a probe that misses once is asked again, and a
    peer that never answers is suspected as before."""

    @pytest.mark.parametrize("peer_fault,suspected", [
        ("cold_ticket", False), ("one_miss", False), ("silent", True)])
    def test_what_a_gather_suspects(self, cluster, monkeypatch,
                                    peer_fault, suspected):
        from ceph_tpu.osd import standalone
        acting = [int(o) for o in
                  cluster.client().osdmap.pg_to_up_acting_osds(1, 0)[2]]
        d, peer = cluster.osds[acting[0]], f"osd.{acting[1]}"
        real, asked = standalone.RemoteStore.omap_get, []

        def faulty(rs, *args):
            if rs._peer == peer:
                asked.append(args)
                if peer_fault == "cold_ticket":
                    raise standalone.AuthorizeDeferred("ticket not warm")
                if peer_fault == "silent" or len(asked) == 1:
                    raise ConnectionError(f"rpc to {peer} timed out")
            return real(rs, *args)
        monkeypatch.setattr(standalone.RemoteStore, "omap_get", faulty)
        d.suspect.clear()
        _, _, quorum_ok = d._load_meta(0, acting)
        assert quorum_ok                   # the other peer answered
        assert (acting[1] in d.suspect) == suspected
        # a cold ticket is not asked again; a silent peer once more
        assert len(asked) == {"cold_ticket": 1, "silent": 2}.get(
            peer_fault, len(asked))
