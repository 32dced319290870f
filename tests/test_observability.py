"""Observability plane, end to end against a LIVE wire cluster.

Tier-1 smoke of ISSUE 4's acceptance surface: a real standalone
cluster (cephx + secure frames ON) is booted once per module, then

* the Unix admin socket answers `perf dump` / `dump_historic_ops` /
  `log dump` with counters from the instrumented hot paths
  (msgr / op-window / ec / cephx);
* every counter name any daemon emits was DECLARED through
  PerfCountersBuilder (catches dynamic/typo'd names in hand-assembled
  dumps);
* `ceph_cli.py --asok-dir <dir> status / health / prometheus` renders
  from MgrReport-aggregated real daemon counters, not sim-synthesized
  values;
* a seeded fault flips the SLOW_OPS and OSD_DOWN health checks.
"""

import json
import os
import time

import pytest

from ceph_tpu.utils.admin_socket import (AdminSocketError,
                                         admin_command)
from ceph_tpu.utils.perf_counters import is_declared


@pytest.fixture(scope="module")
def cluster():
    from ceph_tpu.osd.standalone import StandaloneCluster
    c = StandaloneCluster(n_osds=4, pg_num=2, cephx=True,
                          secret=os.urandom(32))
    c.wait_for_clean(timeout=40)
    yield c
    c.shutdown()


@pytest.fixture(scope="module")
def client(cluster):
    cl = cluster.client()
    objs = {f"obs-{i}": bytes([i % 251]) * (200 + i) for i in range(8)}
    cl.write(objs)
    for name in objs:
        assert cl.read(name) == objs[name]
    return cl


def _wait_for(pred, timeout, what):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        got = pred()
        if got:
            return got
        time.sleep(0.2)
    raise TimeoutError(what)


class TestAdminSocket:
    def test_stop_ends_the_accept_thread_and_lets_its_owner_go(
            self, tmp_path):
        """A stopped socket's accept thread ends, so what its commands
        reach (a daemon, and through it its cluster) can be freed: a
        process that boots a cluster again does not keep the old one."""
        import gc
        import threading
        import weakref
        from ceph_tpu.utils.admin_socket import AdminSocket

        class Owner:
            def status(self, args):
                return {"up": True}
        owner = Owner()
        gone = weakref.ref(owner)
        sock = AdminSocket(str(tmp_path / "osd.99.asok"))
        sock.register("status", owner.status)
        sock.start()
        assert admin_command(sock.path, "status") == {"up": True}
        del owner
        sock.stop()
        _wait_for(lambda: not any(t.name == "osd.99-asok"
                                  for t in threading.enumerate()),
                  5.0, "the accept thread to end")
        del sock
        gc.collect()
        assert gone() is None

    def test_perf_dump_has_hot_path_counters(self, cluster, client):
        """`ceph daemon osd.N perf dump` over the Unix socket returns
        msgr/op-window/ec counters that actually moved under the I/O
        the client just did."""
        perf = admin_command(cluster.asok_path("osd.0"), "perf dump")
        assert perf["msgr"]["frames_tx"] > 0
        assert perf["msgr"]["frames_rx"] > 0
        assert perf["msgr"]["bytes_tx"] > 0
        # secure mode: seal/open time accumulated per frame
        assert perf["msgr"]["seal_time"]["avgcount"] > 0
        # ack coalescing: far fewer acks than frames received
        assert 0 < perf["msgr"]["acks_tx"] < perf["msgr"]["frames_rx"]
        assert perf["rpc"]["op_send"] > 0
        assert perf["cephx"]["ticket_fetches"] > 0
        # some daemon primaried a PG and encoded writes — via the
        # fused device launch OR the r13 host-encode fast path
        # (native SSE on the CPU backend), whichever served this box
        total_enc = 0
        for o in cluster.osd_ids():
            ec = admin_command(cluster.asok_path(f"osd.{o}"),
                               "perf dump")["ec"]
            total_enc += (ec["fused_write_launches"]
                          + ec["host_encode_launches"])
        assert total_enc > 0

    def test_every_emitted_counter_was_declared(self, cluster, client):
        """The declared-name invariant: every (logger, key) a daemon's
        perf dump emits exists in the PerfCountersBuilder registry —
        a hand-assembled/typo'd counter name fails here."""
        for osd in cluster.osd_ids():
            perf = admin_command(cluster.asok_path(f"osd.{osd}"),
                                 "perf dump")
            for logger, counters in perf.items():
                for key in counters:
                    assert is_declared(logger, key), \
                        f"{logger}.{key} emitted but never declared"
        mon_perf = admin_command(cluster.asok_path("mon.0"),
                                 "perf dump")["mon.0"]
        for logger, counters in mon_perf.items():
            for key in counters:
                assert is_declared(logger, key), \
                    f"mon {logger}.{key} emitted but never declared"

    def test_codec_counters_in_perf_dump(self, cluster, client,
                                         monkeypatch):
        """The process-wide `codec` logger: a host-face call under the
        line raises `host_face_calls` only, one over it the pipelined and
        lease counters too, and a daemon's `perf dump` and `perf schema` list
        the logger beside the daemon's own."""
        import numpy as np

        from ceph_tpu.ec.registry import factory
        from ceph_tpu.ops import rs_kernels as K

        def codec():
            return admin_command(cluster.asok_path("osd.0"),
                                 "perf dump")["codec"]

        coder = factory("plugin=jerasure technique=reed_sol_van k=4 m=2")
        data = np.arange(6 * 4 * 512, dtype=np.uint8).reshape(6, 4, 512)
        before = codec()
        coder.encode_chunks(data)               # 12 KiB: one launch
        small = codec()
        assert small["host_face_calls"] == before["host_face_calls"] + 1
        assert small["host_face_bytes_in"] \
            == before["host_face_bytes_in"] + data.size
        assert small["host_face_pipelined_calls"] \
            == before["host_face_pipelined_calls"]
        assert small["host_face_sub_batches"] \
            == before["host_face_sub_batches"]
        for lease in ("host_face_results_fresh", "host_face_results_recycled"):
            assert small[lease] == before[lease]
        # the line lowered to two rows a sub-batch: 6 rows go as 3
        monkeypatch.setattr(K, "_SUB_BATCH_BYTES", 2 * 4 * 512)
        coder.encode_chunks(data)
        large = codec()
        assert large["host_face_calls"] == small["host_face_calls"] + 1
        assert large["host_face_pipelined_calls"] \
            == small["host_face_pipelined_calls"] + 1
        assert large["host_face_sub_batches"] \
            == small["host_face_sub_batches"] + 3
        # the coder's face had lent no result of this shape before
        assert large["host_face_results_fresh"] \
            == small["host_face_results_fresh"] + 1
        assert large["host_face_results_recycled"] \
            == small["host_face_results_recycled"]
        schema = admin_command(cluster.asok_path("osd.0"), "perf schema")
        assert schema["codec"]["host_face_calls"]["kind"] == "counter"
        assert set(large) == set(schema["codec"])
        # the daemon's own loggers are there as before
        assert "msgr" in schema and "ec" in schema

    def test_historic_ops_and_log_dump(self, cluster, client):
        p = cluster.asok_path("osd.0")
        # some osd served client ops; find one with history
        hists = [admin_command(cluster.asok_path(f"osd.{o}"),
                               "dump_historic_ops")
                 for o in cluster.osd_ids()]
        assert any(h["num_ops"] > 0 for h in hists)
        busy = next(h for h in hists if h["num_ops"] > 0)
        events = [e["event"] for e in
                  busy["ops"][0]["type_data"]["events"]]
        assert "reached_pg" in events and "done" in events
        lines = admin_command(p, "log dump")["lines"]
        assert isinstance(lines, list)
        assert admin_command(p, "dump_ops_in_flight")["num_ops"] == 0
        assert "complaint_time" in admin_command(p, "slow_ops")

    def test_perf_schema_reset_help_unknown(self, cluster, client):
        p = cluster.asok_path("osd.1")
        schema = admin_command(p, "perf schema")
        assert schema["msgr"]["frames_tx"]["kind"] == "counter"
        assert schema["msgr"]["seal_time"]["kind"] == "time_avg"
        helps = admin_command(p, "help")
        assert "perf dump" in helps and "log dump" in helps
        before = admin_command(p, "perf dump")["msgr"]["frames_tx"]
        assert admin_command(p, "perf reset") == {"success": True}
        after = admin_command(p, "perf dump")["msgr"]["frames_tx"]
        # heartbeats keep ticking between reset and dump, so "less
        # than the whole boot history" is the stable claim
        assert after < before
        with pytest.raises(AdminSocketError, match="unknown command"):
            admin_command(p, "definitely not a command")

    def test_wire_admin_op_same_dispatcher(self, cluster, client):
        """The legacy wire `admin` MOSDOp serves the SAME extended
        command set (one dispatcher, two surfaces)."""
        out = client.daemon(2, "config show")
        assert "osd_op_complaint_time" in out
        perf = client.daemon(2, "perf dump")
        assert "msgr" in perf and "rpc" in perf


class TestMgrAggregation:
    def test_status_health_from_real_reports(self, cluster, client):
        """`ceph status` renders from MgrReport-aggregated daemon
        counters: every OSD + at least one mon reporting, PGs
        active+clean, HEALTH_OK."""
        st = _wait_for(
            lambda: (s := client.status())["daemons_reporting"]
            >= cluster.n_osds + 1 and s["health"] == "HEALTH_OK"
            and s,
            30, "all daemons reporting + HEALTH_OK")
        assert st["osds_up"] == cluster.n_osds
        assert st["pg_states"].get("active+clean") == cluster.pg_num
        assert st["mon_leader"] == 0
        h = client.health(detail=True)
        assert h["status"] == "HEALTH_OK" and h["checks"] == []

    def test_prometheus_from_aggregated_counters(self, cluster,
                                                 client):
        text = _wait_for(
            lambda: (t := client.prometheus_text())
            and 'ceph_tpu_osd_op{daemon="osd.' in t and t,
            30, "osd counters in exposition")
        # per-daemon labels over the REAL counters
        assert '# TYPE ceph_tpu_msgr_frames_tx counter' in text
        assert 'ceph_tpu_rpc_op_send{daemon=' in text
        assert 'ceph_tpu_mon_' in text          # control plane too
        # every sample line parses as name{labels} value
        for line in text.strip().splitlines():
            if not line.startswith("#"):
                assert len(line.rsplit(" ", 1)) == 2, line
        # and the op counter really carries the I/O we did
        total_op = sum(
            float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("ceph_tpu_osd_op{"))
        assert total_op >= 8                    # the writes + reads

    def test_ceph_cli_live_mode(self, cluster, client, capsys):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "tools"))
        import ceph_cli
        _wait_for(lambda: client.status()["daemons_reporting"]
                  >= cluster.n_osds, 30, "daemons reporting")
        ceph_cli.main(["--asok-dir", cluster.admin_dir, "status"])
        out = capsys.readouterr().out
        assert "health:" in out and "osd:" in out
        ceph_cli.main(["--asok-dir", cluster.admin_dir, "--json",
                       "health", "detail"])
        h = json.loads(capsys.readouterr().out)
        assert h["status"] in ("HEALTH_OK", "HEALTH_WARN")
        ceph_cli.main(["--asok-dir", cluster.admin_dir, "prometheus"])
        assert "ceph_tpu_osd_op{" in capsys.readouterr().out
        ceph_cli.main(["--asok-dir", cluster.admin_dir, "--json",
                       "daemon", "osd.0", "perf", "dump"])
        perf = json.loads(capsys.readouterr().out)
        assert "msgr" in perf


class TestChaosLogRouting:
    def test_thrasher_events_land_in_log_ring(self):
        """Thrasher events ride `dout("chaos", ...)` with the seed in
        every line, so `log dump` over any admin socket reconstructs
        the fault timeline (gathered, not printed)."""
        from ceph_tpu.chaos.thrasher import Thrasher
        from ceph_tpu.utils.log import g_log
        th = Thrasher(seed=4242)          # no cluster boot needed
        th._log("kill osd.1")
        th._log("revive osd.1")
        lines = [ln for ln in g_log.dump_recent()
                 if "thrash seed=4242" in ln]
        assert any("kill osd.1" in ln for ln in lines)
        assert any("revive osd.1" in ln for ln in lines)
        # events were gathered, not printed (chaos log level is 0)
        assert th.schedule == ["kill osd.1", "revive osd.1"]


class TestHealthFlips:
    def test_slow_ops_flip(self, cluster, client):
        """SLOW_OPS: a config-tuned complaint time + a genuinely
        in-flight op flips the check through the REAL report path
        (daemon OpTracker -> MgrReport -> monitor health)."""
        client.config_set("osd_op_complaint_time", 0.05, timeout=20)
        d = cluster.osds[0]
        op = d.op_tracker.create_op("wedged op (test)")
        try:
            h = _wait_for(
                lambda: (hh := client.health(detail=True))
                and any(c["code"] == "SLOW_OPS"
                        for c in hh["checks"]) and hh,
                30, "SLOW_OPS raised")
            slow = next(c for c in h["checks"]
                        if c["code"] == "SLOW_OPS")
            assert any("osd.0" in line for line in slow["detail"])
        finally:
            op.finish()
            client.config_rm("osd_op_complaint_time", timeout=20)
        _wait_for(
            lambda: not any(c["code"] == "SLOW_OPS"
                            for c in client.health()["checks"]),
            30, "SLOW_OPS cleared")

    def test_osd_down_flip(self, cluster, client):
        """OSD_DOWN: a killed daemon flips health through the real
        failure-detection path, and the check clears on revive."""
        victim = 3
        cluster.kill_osd(victim)
        try:
            cluster.wait_for_down(victim, timeout=40)
            h = _wait_for(
                lambda: (hh := client.health(detail=True))
                and any(c["code"] == "OSD_DOWN"
                        for c in hh["checks"]) and hh,
                30, "OSD_DOWN raised")
            down = next(c for c in h["checks"]
                        if c["code"] == "OSD_DOWN")
            assert f"osd.{victim} is down" in down["detail"]
        finally:
            cluster.revive_osd(victim)
        _wait_for(
            lambda: client.status()["osds_up"] == cluster.n_osds,
            40, "revived osd back up")
        cluster.wait_for_clean(timeout=40)
