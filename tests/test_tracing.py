"""Distributed tracing plane (r15), units + end to end.

Tier-1 keeps ONE full-cluster boot (the module fixture below — the
representative cell, like test_observability's); heavier cells that
boot their own cluster are slow-marked per the r15 CI satellite.

Covered:
* flight-recorder ring semantics (bound, eviction accounting, drain
  cursor, declared-span-name registry, retroactive TrackedOp capture);
* TraceContext wire form (roundtrip, cost snapshot, malformed-blob
  tolerance) — the frame-level version gating lives in
  tests/test_msgr_frames.py;
* TraceAssembler: cross-daemon stitching, critical-path attribution
  (self-time vs concurrent children, wire gap), Chrome export, LRU
  bound;
* LIVE cluster (cephx + secure): a sampled write/read assembles into
  ONE trace spanning client + primary + replica with queue/encode/
  crypto/store spans; every recorded span name was declared (the r9
  invariant extended to the trace plane); an op crossing
  osd_op_complaint_time is retroactively assembled from the rings;
  the client cost snapshot biases the repair planner's helper costs;
  ceph_cli trace renders valid Chrome trace-event JSON.
"""

import json
import os
import time

import pytest

from ceph_tpu.mgr.tracing import (TraceAssembler, chrome_trace_events,
                                  critical_path)
from ceph_tpu.utils.flight_recorder import (FlightRecorder,
                                            TraceContext, activate,
                                            is_span_declared,
                                            new_trace_id)
# the flight ring's one way in (`trace_span` is private to it since PR 36)
from ceph_tpu.utils.tracing import span as trace_span


def _span(trace, sid, parent, name, daemon, start, dur, **tags):
    return {"trace_id": f"{trace:016x}", "span_id": f"{sid:016x}",
            "parent_id": f"{parent:016x}", "name": name,
            "daemon": daemon, "start": start, "dur": dur,
            "tags": tags or None}


class TestFlightRecorder:
    def test_ring_bounds_and_eviction_accounting(self):
        fr = FlightRecorder("osd.9", capacity=16)
        for i in range(40):
            fr.record(7, 100 + i, 0, "osd.op", 1000.0 + i, 0.001)
        d = fr.dump()
        assert len(d["spans"]) == 16
        assert d["recorded"] == 40 and d["dropped"] == 24
        # nothing was drained, so every eviction lost unshipped spans
        assert d["dropped_unshipped"] == 24

    def test_drain_cursor(self):
        fr = FlightRecorder("osd.9", capacity=64)
        for i in range(5):
            fr.record(7, i + 1, 0, "osd.op", 1000.0, 0.001)
        got = fr.drain()
        assert len(got) == 5
        assert fr.drain() == []           # cursor advanced
        fr.record(7, 99, 0, "osd.op", 1001.0, 0.001)
        assert len(fr.drain()) == 1
        assert fr.pending_ship() == 0

    def test_trace_filter_and_hex_normalization(self):
        fr = FlightRecorder("osd.9")
        fr.record(0xAB, 1, 0, "osd.op", 1.0, 0.1)
        fr.record(0xCD, 2, 0, "osd.op", 1.0, 0.1)
        assert len(fr.dump(trace_id=0xAB)["spans"]) == 1
        assert len(fr.dump(trace_id="ab")["spans"]) == 1
        assert len(fr.dump(trace_id="0xAB")["spans"]) == 1

    def test_trace_span_noop_without_sampled_ctx(self):
        fr = FlightRecorder("osd.9")
        with trace_span("osd.op"):            # no ctx at all
            pass
        with activate(TraceContext(5, 1, sampled=False), fr):
            with trace_span("osd.op"):        # unsampled ctx
                pass
        assert fr.dump()["spans"] == []

    def test_nested_spans_parent_chain(self):
        fr = FlightRecorder("osd.9")
        ctx = TraceContext(new_trace_id(), 42, sampled=True)
        with activate(ctx, fr):
            with trace_span("osd.op"):
                with trace_span("store.apply"):
                    pass
        spans = {s["name"]: s for s in fr.dump()["spans"]}
        outer, inner = spans["osd.op"], spans["store.apply"]
        assert outer["parent_id"] == f"{42:016x}"
        assert inner["parent_id"] == outer["span_id"]
        assert inner["trace_id"] == outer["trace_id"]

    def test_record_tracked_retro_spans(self):
        from ceph_tpu.utils.op_tracker import OpTracker
        tr = OpTracker()
        op = tr.create_op("osd_op(write) client=client.0")
        op.mark_event("reached_pg")
        op.mark_event("weird_custom_event")
        op.mark_event("commit_sent")
        op.finish()
        fr = FlightRecorder("osd.9")
        ctx = TraceContext(0xBEEF, 7, sampled=False)
        fr.record_tracked(op, ctx)
        spans = fr.dump(trace_id=0xBEEF)["spans"]
        names = sorted(s["name"] for s in spans)
        # allowlisted events become spans; unknown ones fold into tags
        assert "retro.op" in names
        assert "retro.reached_pg" in names and "retro.done" in names
        assert "retro.weird_custom_event" not in names
        root = next(s for s in spans if s["name"] == "retro.op")
        assert root["tags"]["retro"] is True
        assert any("weird_custom_event" in e
                   for e in root["tags"]["events"])
        assert all(is_span_declared(s["name"]) for s in spans)

    def test_live_capacity_via_config(self):
        from ceph_tpu.utils.config import Config
        cfg = Config()
        fr = FlightRecorder("osd.9", config=cfg)
        assert fr.capacity == cfg["osd_trace_ring_size"]
        cfg.set("osd_trace_ring_size", 32)
        assert fr.capacity == 32


class TestTraceContextWire:
    def test_roundtrip_with_cost_snapshot(self):
        ctx = TraceContext(new_trace_id(), new_trace_id(), True,
                           client_lat={0: 0.004, 3: 1.25},
                           client_suspects=(3,))
        got = TraceContext.decode(ctx.encode())
        assert got.trace_id == ctx.trace_id
        assert got.parent_span_id == ctx.parent_span_id
        assert got.sampled
        assert got.client_suspects == (3,)
        assert abs(got.client_lat[3] - 1.25) < 1e-6

    def test_unsampled_is_compact_and_strips_snapshot(self):
        ctx = TraceContext(9, 8, False,
                           client_lat={0: 1.0}, client_suspects=(1,))
        raw = ctx.encode()
        assert len(raw) == 17      # the off-sample wire cost
        got = TraceContext.decode(raw)
        assert not got.sampled and got.client_lat is None

    def test_malformed_blob_decodes_to_none(self):
        assert TraceContext.decode(b"") is None
        assert TraceContext.decode(b"\x00" * 5) is None
        assert TraceContext.decode(b"\x00" * 17) is None   # id 0
        # truncated cost section: tolerated, not fatal
        ctx = TraceContext(5, 6, True, client_lat={1: 0.5})
        assert TraceContext.decode(ctx.encode()[:-3]) is None


class TestAssembler:
    def _three_daemon_trace(self, tid=0x77):
        # client root 0..100ms; osd.queue 10..20; osd.op 20..80 with
        # nested encode 25..45 and two CONCURRENT subops 50..70 — the
        # overlap must not double-subtract from osd.op's self time
        root = _span(tid, 1, 0, "client.op", "client.0", 0.0, 0.100)
        q = _span(tid, 2, 1, "osd.queue", "osd.0", 0.010, 0.010)
        op = _span(tid, 3, 1, "osd.op", "osd.0", 0.020, 0.060)
        enc = _span(tid, 4, 3, "ecbackend.write.encode", "osd.0",
                    0.025, 0.020)
        s1 = _span(tid, 5, 3, "osd.subop", "osd.1", 0.050, 0.020)
        s2 = _span(tid, 6, 3, "osd.subop", "osd.2", 0.050, 0.020)
        return [root, q, op, enc, s1, s2]

    def test_critical_path_attribution(self):
        cp = critical_path(self._three_daemon_trace())
        assert abs(cp["total"] - 0.100) < 1e-9
        assert abs(cp["queue"] - 0.010) < 1e-9
        assert abs(cp["encode"] - 0.020) < 1e-9
        assert abs(cp["store"] - 0.040) < 1e-9   # both subops' self
        # osd.op self = 60 - union(encode 20 + subops 20 overlapped)
        assert abs(cp["other"] - 0.020) < 1e-9
        # wire = root 100 - union of descendants (10..80) = 30
        assert abs(cp["wire"] - 0.030) < 1e-9

    def test_assemble_and_chrome_export(self):
        asm = TraceAssembler()
        spans = self._three_daemon_trace()
        asm.ingest(spans[:3])
        asm.ingest(spans[3:])
        asm.ingest(spans)                 # re-ship: dedup, no growth
        out = asm.assemble(f"{0x77:016x}")
        assert out["found"] and len(out["spans"]) == 6
        assert out["daemons"] == ["client.0", "osd.0", "osd.1",
                                  "osd.2"]
        ev = out["chrome"]["traceEvents"]
        json.dumps(ev)                    # valid JSON
        meta = [e for e in ev if e["ph"] == "M"]
        xs = [e for e in ev if e["ph"] == "X"]
        assert len(meta) == 4 and len(xs) == 6
        assert all({"name", "ts", "dur", "pid", "tid"} <= set(e)
                   for e in xs)
        # slow view carries the attribution
        slow = asm.slow()
        assert slow and slow[0]["critical_path"]["total"] > 0

    def test_lru_eviction_bound(self):
        asm = TraceAssembler(max_traces=4)
        for t in range(10):
            asm.ingest([_span(t + 1, 1, 0, "client.op", "c",
                              float(t), 0.001)])
        assert len(asm.list_traces()) == 4
        assert not asm.assemble(f"{1:016x}")["found"]   # evicted
        assert asm.assemble(f"{10:016x}")["found"]


# -- live cluster (the tier-1 representative cell) ---------------------------

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
    cl.trace_sample_rate = 1.0      # constructor-level override
    return cl


def _wait_for(pred, timeout, what):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        got = pred()
        if got:
            return got
        time.sleep(0.2)
    raise TimeoutError(what)


class TestLiveTracing:
    def test_sampled_op_assembles_across_daemons(self, cluster,
                                                 client, tmp_path,
                                                 capsys):
        """The r15 acceptance path: one sampled write+read on a live
        cephx+secure cluster assembles into ONE trace spanning client,
        primary and at least one replica/helper, with queue/encode/
        crypto/store spans, exported as valid Chrome trace-event JSON
        through ceph_cli trace."""
        objs = {f"trace-{i}": bytes([i]) * 1500 for i in range(6)}
        client.write(objs)
        assert client.read("trace-1") == objs["trace-1"]
        tid = f"{client.last_trace_id:016x}"
        assert client.last_trace_id != 0
        client._flush_trace_spans(force=True)

        def assembled():
            for m in cluster.mons:
                a = m.traces.assemble(tid)
                # the daemons' reports can reach a monitor before the
                # client's flush does: wait for both
                if a["found"] and len(a["daemons"]) >= 2 and any(
                        d.startswith("client.") for d in a["daemons"]):
                    return a
            return None
        asm = _wait_for(assembled, 30, "trace assembled on a monitor")
        # the write traces span 3+ daemons; the read (the LAST sampled
        # trace) touches client + the shard sources it gathered from
        assert any(d.startswith("client.") for d in asm["daemons"])
        assert sum(d.startswith("osd.") for d in asm["daemons"]) >= 1
        names = {s["name"] for s in asm["spans"]}
        assert "client.op" in names
        cp = asm["critical_path"]
        assert cp["total"] > 0
        assert set(cp) >= {"queue", "crypto", "encode", "store",
                           "wire", "other", "total"}
        # a WRITE trace from the primary's ring covers >= 3 daemons
        # (client + primary + replica store applies). mon.0
        # specifically: ceph_cli's live mode asks it first.
        # (each daemon reports on its own: wait until the primary's
        # and a replica's spans are both in)
        def wide_trace():
            for t in cluster.mons[0].traces.list_traces():
                if len(t["daemons"]) >= 3:
                    a = cluster.mons[0].traces.assemble(t["trace_id"])
                    if {"osd.queue", "osd.subop"} <= {
                            s["name"] for s in a["spans"]}:
                        return a
            return None
        wide = _wait_for(wide_trace, 30, "a >=3-daemon trace assembled")
        assert len(wide["daemons"]) >= 3
        wide_names = {s["name"] for s in wide["spans"]}
        assert ("ecbackend.write.encode" in wide_names
                or "ecbackend.read.decode" in wide_names
                or "msgr.seal" in wide_names)
        # ceph_cli trace: human view + Chrome export
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "tools"))
        import ceph_cli
        ceph_cli.main(["--asok-dir", cluster.admin_dir, "trace",
                       wide["trace_id"]])
        out = capsys.readouterr().out
        assert wide["trace_id"] in out and "attribution:" in out
        chrome = str(tmp_path / "trace.json")
        ceph_cli.main(["--asok-dir", cluster.admin_dir, "trace",
                       wide["trace_id"], "--chrome", chrome])
        assert "wrote" in capsys.readouterr().out
        with open(chrome) as f:
            data = json.load(f)
        assert data["traceEvents"]
        assert all(e["ph"] in ("X", "M") for e in data["traceEvents"])
        # `trace slow` lists assembled traces with attribution
        ceph_cli.main(["--asok-dir", cluster.admin_dir, "--json",
                       "trace", "slow"])
        slow = json.loads(capsys.readouterr().out)
        assert slow["traces"]
        assert "critical_path" in slow["traces"][0]

    def test_every_span_name_was_declared(self, cluster, client):
        """The r9 no-undeclared-names invariant, extended to the
        trace plane: every span name in every daemon's ring (client
        included) exists in the declared-span registry."""
        rings = [d.flight.dump() for d in cluster.osds.values()
                 if not d._stop.is_set()]
        rings.append(client.flight.dump())
        checked = 0
        for dump in rings:
            for s in dump["spans"]:
                assert is_span_declared(s["name"]), \
                    f"{dump['daemon']}: span {s['name']!r} recorded " \
                    f"but never declared"
                checked += 1
        assert checked > 0

    def test_trace_dump_admin_command(self, cluster, client):
        from ceph_tpu.utils.admin_socket import admin_command
        busy = next(d for d in cluster.osds.values()
                    if d.flight.dump()["spans"])
        dump = admin_command(cluster.asok_path(busy.name),
                             "trace dump")
        assert dump["daemon"] == busy.name and dump["spans"]
        one = dump["spans"][0]["trace_id"]
        filt = admin_command(cluster.asok_path(busy.name),
                             f"trace dump {one}")
        assert filt["spans"]
        assert all(s["trace_id"] == one for s in filt["spans"])

    def test_retroactive_slow_op_assembled_from_rings(self, cluster,
                                                      client):
        """An UNSAMPLED op crossing osd_op_complaint_time converts its
        OpTracker events into retro.* ring spans under the carried
        trace id — assembling the rings yields its timeline."""
        client.config_set("osd_op_complaint_time", 0.0001, timeout=20)
        try:
            _wait_for(
                lambda: all(
                    d.op_tracker.complaint_time < 0.001
                    for d in cluster.osds.values()
                    if not d._stop.is_set()),
                20, "complaint time committed")
            client.trace_sample_rate = 0.0    # stamp, never sample
            client.write({"retro-obj": b"R" * 60000})

            def retro_spans():
                out = []
                for d in cluster.osds.values():
                    if d._stop.is_set():
                        continue
                    out += [s for s in d.flight.dump()["spans"]
                            if s["name"] == "retro.op"]
                return out
            spans = _wait_for(retro_spans, 10, "retro spans recorded")
            tid = spans[-1]["trace_id"]
            asm = TraceAssembler()
            for d in cluster.osds.values():
                if not d._stop.is_set():
                    asm.ingest(d.flight.dump(trace_id=tid)["spans"])
            asm.ingest(client.flight.dump(trace_id=tid)["spans"])
            got = asm.assemble(tid)
            assert got["found"]
            names = {s["name"] for s in got["spans"]}
            assert "retro.op" in names and "retro.reached_pg" in names
        finally:
            client.trace_sample_rate = 1.0
            client.config_rm("osd_op_complaint_time", timeout=20)

    def test_hedged_dispatch_is_always_sampled(self, cluster, client):
        """Hedged/degraded dispatches force sampling and carry the
        client's cost snapshot + complaint set."""
        client.trace_sample_rate = 0.0         # probabilistic OFF
        try:
            client._note_latency("osd.1", 0.025)
            client._suspect_target("osd.2")
            ctx = client._make_trace_ctx(force=True)
            assert ctx is not None and ctx.sampled
            assert abs(ctx.client_lat[1] - 0.025) < 1e-6
            assert 2 in ctx.client_suspects
            # probabilistic path at rate 0: stamped but unsampled
            plain = client._make_trace_ctx()
            assert plain is not None and not plain.sampled
            # rate < 0 disables stamping entirely
            client.trace_sample_rate = -1.0
            assert client._make_trace_ctx() is None
            assert client._make_trace_ctx(force=True) is None
        finally:
            client.trace_sample_rate = 1.0
            client._tgt_suspect.pop("osd.2", None)

    def test_client_cost_snapshot_biases_helper_costs(self, cluster,
                                                      client):
        """Satellite (r14 follow-up): the shipped client EWMA/
        complaint snapshot folds into the daemon's repair-planner cost
        table — a client-observed-slow helper ranks behind, a
        client-suspected one gets the complaint floor."""
        d = next(d for d in cluster.osds.values()
                 if not d._stop.is_set() and d.backends)
        ps, be = next(iter(d.backends.items()))
        others = [o for o in be.acting if o != d.osd_id]
        slow, suspected = others[0], others[-1]
        base = d._helper_costs(be)
        ctx = TraceContext(new_trace_id(), 0, True,
                           client_lat={slow: 0.5},
                           client_suspects=(suspected,))
        d._note_client_costs(ctx)
        biased = d._helper_costs(be)
        s_slow = be.acting.index(slow)
        assert biased[s_slow] >= int(0.5 * 1e6 * 0.25)  # EWMA blend
        assert biased[s_slow] > base[s_slow]
        s_sus = be.acting.index(suspected)
        assert biased[s_sus] >= 1_000_000     # the 1s complaint floor
        # stale claims age out
        d._client_lat[slow] = (0.5, time.monotonic() - 1e6)
        aged = d._helper_costs(be)
        assert aged[s_slow] == base[s_slow]
        d._client_lat.clear()

    def test_off_sample_ops_record_nothing(self, cluster, client,
                                           monkeypatch):
        """The overhead-guard property in miniature: at sample rate 0
        (contexts stamped, never sampled) a fast op records no span
        anywhere under the trace ids it carried. Keyed by those ids:
        under load a recovery round (sampled at its own rate) or
        another test's trace can land spans in the rings meanwhile.
        Under load the op is not always fast: a read that outlasts the
        client's auto hedge delay (4x its recent p95, floor 150 ms) is
        sent again under a FORCED-sample context, by design
        (test_hedged_dispatch_is_always_sampled), and that one records
        spans. The property is about the probabilistic path, so this
        client hedges nothing while it is read."""
        live = [d for d in cluster.osds.values() if not d._stop.is_set()]
        # the retro test's complaint time is taken back daemon by
        # daemon: until then every op is "slow" and records retro.*
        _wait_for(lambda: all(d.op_tracker.complaint_time > 1.0
                              for d in live),
                  20, "complaint time restored")
        handed = []
        make_ctx = client._make_trace_ctx

        def spy(force: bool = False):
            ctx = make_ctx(force)
            handed.append(ctx)
            return ctx
        monkeypatch.setattr(client, "_make_trace_ctx", spy)
        monkeypatch.setattr(client, "hedge_delay_ms", -1.0)   # < 0: off
        client.trace_sample_rate = 0.0
        try:
            client.write({"offsample": b"x" * 512})
            assert client.read("offsample") == b"x" * 512
        finally:
            client.trace_sample_rate = 1.0
        assert handed and not any(ctx.sampled for ctx in handed)
        mine = {f"{ctx.trace_id:016x}" for ctx in handed}
        rings = [d.flight for d in live] + [client.flight]
        recorded = {
            ring.daemon: [(s["name"], s["dur"])
                          for s in ring.dump()["spans"]
                          if s["trace_id"] in mine]
            for ring in rings}
        assert not any(recorded.values()), recorded


@pytest.mark.slow
def test_tracing_under_sharded_osds_own_cluster(tmp_path):
    """Slow cell (boots its own cluster, per the slow-mark rule):
    2-shard OSDs — batch frames spanning shards still produce
    per-shard osd.queue spans under one trace, and recovery rounds
    after a kill record osd.recovery_round spans whose helper pulls
    hit other daemons' rings."""
    from ceph_tpu.chaos.thrasher import load_factor
    from ceph_tpu.osd.standalone import StandaloneCluster
    lf = load_factor()
    c = StandaloneCluster(n_osds=4, pg_num=4, cephx=True,
                          secret=os.urandom(32), op_shards=2,
                          hb_grace=1.2 * lf)
    try:
        c.wait_for_clean(timeout=40 * lf)
        cl = c.client(trace_sample_rate=1.0)
        cl.write({f"sh-{i}": bytes([i]) * 900 for i in range(16)})
        queue_spans = [
            s for d in c.osds.values() if not d._stop.is_set()
            for s in d.flight.dump()["spans"]
            if s["name"] == "osd.queue"]
        assert queue_spans
        victim = max(o for o in c.osd_ids()
                     if not c.osds[o]._stop.is_set())
        c.kill_osd(victim)
        c.wait_for_down(victim, timeout=40 * lf)
        c.wait_for_clean(timeout=90 * lf)
        rec = [s for d in c.osds.values() if not d._stop.is_set()
               for s in d.flight.dump()["spans"]
               if s["name"] == "osd.recovery_round"]
        assert rec, "recovery rounds should trace at the default rate"
        # the round's trace reached a helper's ring (subop spans
        # under the same trace id)
        tids = {s["trace_id"] for s in rec}
        helper_hits = [
            s for d in c.osds.values() if not d._stop.is_set()
            for s in d.flight.dump()["spans"]
            if s["name"] in ("osd.subop", "store.apply")
            and s["trace_id"] in tids]
        assert helper_hits
    finally:
        c.shutdown()
