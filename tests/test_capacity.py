"""Capacity-exhaustion robustness plane (r21) — the full-ratio
ladder live over the wire tier.

Refs: OSDMonitor::update_full_status + get_full_ratios (the ladder),
Objecter full-wait semantics (a FULL cluster PARKS mutations, never
errors them — CEPH_OSD_FLAG_FULL_TRY / implicit-on-delete excepted),
OSDService::check_full_status (the osd_failsafe_full_ratio local
hard-stop), and pg_pool_t quotas -> POOL_FULL.

Everything here drives REAL state: store statfs claims ride the
MgrReport pipe, the leader's capacity tick commits ladder deltas into
the map, clients observe flags through their map subscription. The
ENOSPC txn-phase matrix at the bottom proves the store keeps every
abort atomic (fsck-clean across SIGKILL at any phase)."""

import errno
import threading
import time

import numpy as np
import pytest

from ceph_tpu.chaos import load_factor
from ceph_tpu.osd.memstore import Transaction
from ceph_tpu.osd.standalone import StandaloneCluster
from ceph_tpu.osd.tinstore import TinStore

_LF = load_factor()


def corpus(seed, n=20, size=700, prefix="cap"):
    rng = np.random.default_rng(seed)
    return {f"{prefix}-{seed}-{i}":
            rng.integers(0, 256, size, np.uint8).tobytes()
            for i in range(n)}


def _poll(pred, timeout, what):
    deadline = time.monotonic() + timeout * _LF
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise AssertionError(f"timeout waiting for {what}")


def _checks(cl):
    return {c["code"]: c for c in cl.health()["checks"]}


def _claim_ratio(c, ratio, total=10 << 20):
    """Spoof every live store's statfs CLAIM (what rides MgrReport)
    at a fixed ratio, leaving the store itself unbounded — isolates
    the mon ladder / client parking / recovery gating from raw store
    ENOSPC, which has its own cells (TestFailsafe, TestEnospcTxnMatrix
    and the chaos tier's disk_full stream exercise real capacity)."""
    for d in c.osds.values():
        d.store.statfs = (lambda t=total, r=ratio: {
            "total": t, "used": int(t * r),
            "avail": max(0, int(t * (1 - r)))})


def _unclaim(c):
    for d in c.osds.values():
        try:
            del d.store.statfs
        except AttributeError:
            pass


class _Writer:
    """Background client writer: the op must PARK (thread stays alive,
    no exception) while a full flag flies, then drain exactly-once."""

    def __init__(self, cl, objs):
        self.cl, self.objs = cl, objs
        self.errors: list[BaseException] = []
        self.t = threading.Thread(target=self._run, daemon=True)
        self.t.start()

    def _run(self):
        try:
            self.cl.write(self.objs)
        except BaseException as e:   # noqa: BLE001 — any surfaced
            self.errors.append(e)    # error is the test failure

    def assert_parked(self, grace=1.0):
        time.sleep(grace * _LF)
        assert self.t.is_alive(), \
            f"writer finished during the full window ({self.errors})"
        assert not self.errors

    def drain(self, timeout=30.0):
        self.t.join(timeout * _LF)
        assert not self.t.is_alive(), "parked writer never drained"
        assert not self.errors, f"writer surfaced {self.errors}"


class TestStatfsPipe:
    """statfs claims -> MgrReport -> mon df, with bounded stores."""

    def test_df_reports_every_bounded_store(self):
        c = StandaloneCluster(n_osds=4, pg_num=4, op_timeout=3.0,
                              store_capacity=1 << 20)
        try:
            cl = c.client()
            cl.write(corpus(1, n=8))

            def _all_claimed():
                df = cl.mon_command("df")
                rows = [v for k, v in df["osds"].items()
                        if k.startswith("osd.")]
                return len(rows) == 4 and all(
                    r["total"] == 1 << 20 and r["used"] > 0
                    and r["state"] == "ok" for r in rows)
            _poll(_all_claimed, 20, "df rows from all 4 OSDs")
            df = cl.mon_command("df")
            assert df["cluster_full"] is False
            assert df["total_bytes"] == 4 << 20
            assert df["full_ratios"] == {"nearfull": 0.85,
                                         "backfillfull": 0.90,
                                         "full": 0.95,
                                         "failsafe": 0.97}
        finally:
            c.shutdown()


class TestFullLadder:
    """The whole ladder against one cephx+secure cluster: nearfull
    health, FULL parking writes while reads/deletes serve, restore,
    and the exactly-once drain — the r21 acceptance cell."""

    @pytest.fixture
    def cluster(self):
        c = StandaloneCluster(n_osds=4, pg_num=4, op_timeout=3.0,
                              cephx=True,
                              secret=b"sixteen byte key" * 2)
        try:
            c.wait_for_clean(timeout=20)
            yield c
        finally:
            c.shutdown()

    def test_full_parks_writes_serves_reads_drains_exact(self, cluster):
        cl = cluster.client()
        base = corpus(11)
        cl.write(base)
        # claim every OSD at 0.96 — over the full rung (0.95), under
        # the failsafe (0.97) — and wait for the LADDER (not this
        # test) to decide: the leader folds statfs claims through the
        # committed ratios and commits the FULL flag + states
        _claim_ratio(cluster, 0.96)
        _poll(lambda: cl.mon_command("df")["cluster_full"], 30,
              "mon ladder committing the cluster FULL flag")

        def _all_full():
            # the flag flies on the FIRST full claim; the remaining
            # claims land over the next report beats
            df = cl.mon_command("df")
            return all(r["state"] == "full"
                       for k, r in df["osds"].items()
                       if k.startswith("osd."))
        _poll(_all_full, 20, "every OSD state committing as full")
        checks = _checks(cl)
        assert checks["OSD_FULL"]["severity"] == "HEALTH_ERR"
        assert cl.health()["status"] == "HEALTH_ERR"

        # a fresh client parks its writes on the map flag: alive, no
        # error surfaced — the RADOS full-wait contract
        cl2 = cluster.client()
        w = _Writer(cl2, corpus(13, n=4, prefix="parked"))
        w.assert_parked()
        _poll(lambda: (cl2.perf.dump().get("full_backoff_time") or
                       {}).get("avgcount", 0) > 0, 20,
              "parked intervals landing in full_backoff_time")

        # reads keep serving bit-exact under FULL...
        for name, want in base.items():
            assert cl.read(name) == want
        # ...and a delete passes (the implicit FULL_TRY: freeing
        # space is how a full cluster recovers)
        victim = next(iter(base))
        cl.remove([victim])
        with pytest.raises(KeyError):
            cl.read(victim)
        w.assert_parked(grace=0.5)

        # restore -> the ladder clears the flag -> exactly-once drain
        _unclaim(cluster)
        _poll(lambda: not cl.mon_command("df")["cluster_full"], 30,
              "mon ladder clearing the FULL flag")
        w.drain()
        for name, want in w.objs.items():
            assert cl.read(name) == want
        assert "OSD_FULL" not in _checks(cl)

    def test_nearfull_is_warning_only(self, cluster):
        cl = cluster.client()
        base = corpus(17)
        cl.write(base)
        # one OSD claiming ~0.87: nearfull rung only — IO continues
        d = cluster.osds[0]
        d.store.statfs = lambda: {"total": 10 << 20,
                                  "used": int((10 << 20) * 0.87),
                                  "avail": int((10 << 20) * 0.13)}
        _poll(lambda: "OSD_NEARFULL" in _checks(cl), 30,
              "OSD_NEARFULL health check")
        checks = _checks(cl)
        assert checks["OSD_NEARFULL"]["severity"] == "HEALTH_WARN"
        assert "OSD_FULL" not in checks
        assert not cl.mon_command("df")["cluster_full"]
        df = cl.mon_command("df")
        assert df["osds"]["osd.0"]["state"] == "nearfull"
        more = corpus(19, n=4, prefix="nearfull-io")
        cl.write(more)                       # no parking at nearfull
        for name, want in more.items():
            assert cl.read(name) == want
        del d.store.statfs
        _poll(lambda: "OSD_NEARFULL" not in _checks(cl), 30,
              "nearfull state clearing")


class TestFailsafe:
    """osd_failsafe_full_ratio: the OSD's own statfs hard-stop — it
    must bounce mutations even while the committed map carries no
    FULL flag (the stale-map window), and the bounced op must park at
    the client, not error."""

    def test_failsafe_bounces_then_drains_on_restore(self):
        c = StandaloneCluster(n_osds=4, pg_num=4, op_timeout=3.0)
        try:
            cl = c.client()
            # pin the map-level full rung out of reach so the ONLY
            # thing standing between a 0.98-full store and the write
            # is the local failsafe gate
            cl.config_set("mon_osd_full_ratio", "0.999")
            base = corpus(23)
            cl.write(base)
            for d in c.osds.values():
                used = d.store.statfs()["used"]
                d.store.set_capacity(max(1, int(used / 0.98)))
            w = _Writer(cl, corpus(29, n=2, prefix="failsafe"))
            _poll(lambda: sum(d.perf.get("writes_rejected_full")
                              for d in c.osds.values()) > 0, 20,
                  "an OSD failsafe rejection")
            w.assert_parked()
            assert not cl.mon_command("df")["cluster_full"]
            for d in c.osds.values():
                d.store.set_capacity(0)
            # the ladder's state-clear commit bumps the epoch, which
            # un-pins the parked op (a fresh epoch probes exactly once)
            w.drain()
            for name, want in w.objs.items():
                assert cl.read(name) == want
        finally:
            c.shutdown()


class TestPoolQuota:
    """pg_pool_t quotas -> POOL_FULL: quota commits onto the map over
    the wire, the leader's tick trips the flag from MgrReport pool
    aggregates, writes park, deletes free the pool back open."""

    def test_object_quota_round_trip(self):
        c = StandaloneCluster(n_osds=4, pg_num=4, op_timeout=3.0)
        try:
            cl = c.client()
            base = corpus(31, n=10)
            cl.write(base)
            cl.pool_set_quota(max_objects=5)
            _poll(lambda: cl.mon_command(
                "df")["pools"]["1"]["full"], 30,
                "POOL_FULL from the object quota")
            checks = _checks(cl)
            assert checks["POOL_FULL"]["severity"] == "HEALTH_ERR"
            assert not cl.mon_command("df")["cluster_full"]

            w = _Writer(c.client(),
                        corpus(37, n=2, prefix="quota-parked"))
            w.assert_parked()
            # deletes pass the pool flag and free it back open
            names = sorted(base)[:6]
            cl.remove(names)
            _poll(lambda: not cl.mon_command(
                "df")["pools"]["1"]["full"], 30,
                "POOL_FULL clearing after the deletes")
            w.drain()
            for name, want in w.objs.items():
                assert cl.read(name) == want
            # clearing the quota is committed + observable
            cl.pool_set_quota(0, 0)
            assert cl.mon_command(
                "df")["pools"]["1"]["quota_max_objects"] == 0
        finally:
            c.shutdown()


class TestBackfillfullRecovery:
    """The backfillfull rung gates RECOVERY, not client IO: rebuilds
    into an at/over-backfillfull target park (counted), resume when
    the rung clears, and an m-1 stripe overrides the park. The rung
    is driven through spoofed statfs claims so the park/override
    logic is isolated from raw store ENOSPC (the store gate has its
    own cells above and in the chaos tier)."""

    def test_recovery_parks_then_resumes(self):
        # wide code (m=3): a single loss leaves 2 spare, so the
        # rebuild is NOT urgent and must respect the rung
        c = StandaloneCluster(
            n_osds=7, pg_num=4, op_timeout=3.0,
            profile="plugin=tpu_rs k=2 m=3")
        try:
            cl = c.client()
            base = corpus(41)
            cl.write(base)
            _claim_ratio(c, 0.92)
            _poll(lambda: "OSD_BACKFILLFULL" in _checks(cl), 30,
                  "backfillfull states committing")
            victim = cl.osdmap.pg_to_up_acting_osds(1, 0)[2][0]
            c.kill_osd(victim)
            c.wait_for_down(victim)
            _poll(lambda: sum(
                d.repair_policy.counters[
                    "repair_backfillfull_parked"]
                for d in c.osds.values()
                if not d._stop.is_set()) > 0, 30,
                "a rebuild parking on a backfillfull target")
            # reads still serve degraded while recovery is parked
            for name in list(base)[:4]:
                assert cl.read(name) == base[name]
            _unclaim(c)
            _poll(lambda: "OSD_BACKFILLFULL" not in _checks(cl), 30,
                  "backfillfull states clearing")
            c.wait_for_clean(timeout=40)
            for name, want in base.items():
                assert cl.read(name) == want
        finally:
            c.shutdown()

    def test_m1_stripe_overrides_the_park(self):
        # narrow code (m=1): losing one OSD puts stripes at m-1 —
        # the rebuild must push THROUGH backfillfull targets (losing
        # the stripe is strictly worse than an over-full device)
        c = StandaloneCluster(n_osds=6, pg_num=4, op_timeout=3.0)
        try:
            cl = c.client()
            base = corpus(43)
            cl.write(base)
            _claim_ratio(c, 0.92)
            _poll(lambda: "OSD_BACKFILLFULL" in _checks(cl), 30,
                  "backfillfull states committing")
            victim = cl.osdmap.pg_to_up_acting_osds(1, 0)[2][0]
            c.kill_osd(victim)
            c.wait_for_down(victim)
            c.wait_for_clean(timeout=40)     # recovered DESPITE rung
            assert sum(d.repair_policy.counters[
                "repair_backfillfull_parked"]
                for d in c.osds.values()
                if not d._stop.is_set()) == 0
            for name, want in base.items():
                assert cl.read(name) == want
        finally:
            c.shutdown()


_ENOSPC_PHASES = ("txn.apply", "wal.append", "flush.segment-written",
                  "flush.manifest-swapped",
                  "compact.segments-written",
                  "compact.manifest-swapped")


class TestEnospcTxnMatrix:
    """ENOSPC at EVERY TinStore txn phase, then SIGKILL: the abort
    must be atomic (acked txns wholly present, the failed txn wholly
    absent), the directory fsck-clean, and the store must keep
    accepting once space returns — the r21 fault matrix the chaos
    tier samples from."""

    @pytest.mark.parametrize("phase", _ENOSPC_PHASES)
    def test_enospc_then_sigkill_fsck_clean(self, tmp_path, phase):
        path = str(tmp_path / "s")
        # tiny WAL budget + fanout so flush and compaction phases are
        # reached within a few dozen small txns
        st = TinStore(path, wal_max_bytes=2048, kv_fanout=2)
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "base", 0, b"B" * 512))
        fired = {"n": 0}

        def fault(point):
            if point == phase and fired["n"] == 0:
                fired["n"] = 1
                raise OSError(errno.ENOSPC, f"injected at {point}")
        st.set_fault(fault)
        acked = {}
        for i in range(200):
            if fired["n"]:
                break
            name, data = f"o{i}", bytes([i % 251]) * 300
            try:
                st.queue_transaction(
                    Transaction().write("c", name, 0, data))
                acked[name] = data
            except OSError:
                # the injected abort: NOTHING from this txn may
                # survive (checked after the remount below)
                assert name not in acked
        assert fired["n"] == 1, f"phase {phase} never exercised"
        st.crash()                            # SIGKILL: RAM gone
        rep = TinStore.fsck(path)
        assert rep["errors"] == [] and not rep["bad_objects"], \
            (phase, rep)
        st.remount()
        assert bytes(st.read("c", "base")) == b"B" * 512
        for name, data in acked.items():
            assert bytes(st.read("c", name)) == data, (phase, name)
        # space returns: the store takes writes again
        st.set_fault(None)
        st.queue_transaction(
            Transaction().write("c", "post", 0, b"P" * 64))
        assert bytes(st.read("c", "post")) == b"P" * 64
        st.umount()
