"""Multi-tenant workload engine (r20): the declarative profile
grammar, the seed-deterministic op-stream replay contract, and the
live two-tenant smoke — a quiet tenant and a noisy neighbor driving a
cephx+secure cluster, where the noisy tenant's mClock throttle
counters move while the quiet tenant's SLO verdict stays green."""

import os
import time

import pytest

from ceph_tpu.workload import (BUILTIN_PROFILES, OpStream,
                               TenantProfile, WorkloadEngine,
                               builtin_mix, parse_profiles)
from ceph_tpu.workload.profiles import Phase


def _lf() -> float:
    from ceph_tpu.chaos.thrasher import load_factor
    return load_factor()


class TestProfileGrammar:
    def test_roundtrip_and_builtins(self):
        mix = builtin_mix()
        assert [p.name for p in mix] == list(BUILTIN_PROFILES)
        import json
        again = parse_profiles(json.dumps([p.to_dict()
                                           for p in mix]))
        assert [p.to_dict() for p in again] \
            == [p.to_dict() for p in mix]

    def test_validation_errors(self):
        with pytest.raises(ValueError, match="write_mode"):
            TenantProfile(name="x", write_mode="sideways")
        with pytest.raises(ValueError, match="read_fraction"):
            TenantProfile(name="x", read_fraction=1.5)
        with pytest.raises(ValueError, match="exceeds"):
            TenantProfile(name="x", op_size=9000, object_size=4096)
        with pytest.raises(ValueError, match="phase kind"):
            Phase(kind="sinusoid")
        with pytest.raises(ValueError, match="duplicate"):
            parse_profiles([{"name": "a"}, {"name": "a"}])
        with pytest.raises(ValueError):        # bad mclock spec
            TenantProfile(name="x", mclock="5,1")
        with pytest.raises(ValueError, match="unknown profile keys"):
            TenantProfile.from_dict({"name": "x", "iopz": 3})

    def test_phase_program(self):
        ramp = TenantProfile(
            name="r", phases=[Phase(kind="ramp", duration_s=10.0,
                                    from_scale=0.0, to_scale=2.0)])
        assert ramp.scale_at(0.0) == 0.0
        assert ramp.scale_at(5.0) == pytest.approx(1.0)
        burst = Phase(kind="burst", period_s=1.0, duty=0.25,
                      on_scale=4.0, off_scale=0.5)
        assert burst.scale_at(0.1) == 4.0
        assert burst.scale_at(0.9) == 0.5
        # a finite program cycles when shorter than the run
        cyc = TenantProfile(
            name="c", phases=[Phase(duration_s=1.0, scale=3.0),
                              Phase(duration_s=1.0, scale=1.0)])
        assert cyc.scale_at(0.5) == 3.0
        assert cyc.scale_at(1.5) == 1.0
        assert cyc.scale_at(2.5) == 3.0       # wrapped

    def test_entity_and_mclock_table(self):
        p = TenantProfile(name="noisy", mclock="5,1,25")
        assert p.entity == "client.noisy"


class TestStreamDeterminism:
    def test_same_seed_bit_exact(self):
        for p in builtin_mix():
            a = OpStream(p, 42).generate(3.0)
            b = OpStream(p, 42).generate(3.0)
            assert a == b
            assert OpStream.digest(a) == OpStream.digest(b)

    def test_seed_and_tenant_fork_streams(self):
        p = builtin_mix(["interactive"])[0]
        d1 = OpStream.digest(OpStream(p, 1).generate(3.0))
        d2 = OpStream.digest(OpStream(p, 2).generate(3.0))
        assert d1 != d2
        q = TenantProfile.from_dict(
            {**p.to_dict(), "name": "interactive2"})
        d3 = OpStream.digest(OpStream(q, 1).generate(3.0))
        assert d3 != d1       # same seed, different tenant identity

    def test_routing_follows_write_mode(self):
        for mode, kind in (("overwrite", "write_at"),
                           ("append", "append"),
                           ("full", "write_full")):
            p = TenantProfile(name="t", iops=200.0,
                              read_fraction=0.0, op_size=256,
                              object_size=1024, write_mode=mode)
            ops = OpStream(p, 0).generate(1.0)
            assert ops and all(op.kind == kind for op in ops)
            if mode == "overwrite":
                assert all(op.offset + op.size <= 1024
                           for op in ops)

    def test_burst_off_scale_zero_terminates(self):
        p = TenantProfile(
            name="b", iops=100.0,
            phases=[Phase(kind="burst", period_s=0.5, duty=0.2,
                          on_scale=1.0, off_scale=0.0)])
        ops = OpStream(p, 3).generate(2.0)
        assert ops     # thinning handles the zero-rate half-period
        assert all((op.t % 0.5) < 0.1 for op in ops)

    def test_hotspot_concentration(self):
        p = TenantProfile(name="h", iops=300.0, objects=64,
                          hotspot_fraction=0.9, hotspot_objects=2)
        ops = OpStream(p, 5).generate(2.0)
        hot = sum(1 for op in ops if op.obj < 2)
        assert hot / len(ops) > 0.7


class TestLiveTwoTenantSmoke:
    """Tier-1 representative of the r20 engine: two tenants with
    opposing profiles on a LIVE cephx+secure cluster — the noisy
    neighbor demands far beyond its committed mClock limit, the quiet
    tenant stays modest. Asserts the whole attribution chain: seeded
    streams replay bit-exactly, both tenants get latency percentiles,
    the noisy tenant's THROTTLE counter moves, and the quiet tenant's
    tenant-qualified SLO verdict stays green."""

    def test_noisy_neighbor_throttled_quiet_green(self):
        from ceph_tpu.mgr.telemetry import (TelemetryAggregator,
                                            parse_slo_rules)
        from ceph_tpu.osd.standalone import StandaloneCluster
        quiet = TenantProfile(
            name="quiet", klass="interactive", iops=12.0,
            read_fraction=0.6, op_size=(128, 512),
            write_mode="overwrite", objects=4, object_size=2048,
            slo="client_observed_p99 < 10s over 60s")
        noisy = TenantProfile(
            name="noisy", klass="noisy", iops=60.0,
            read_fraction=0.1, op_size=256, write_mode="overwrite",
            objects=4, object_size=2048,
            hotspot_fraction=0.8, hotspot_objects=1,
            mclock="2,1,10",
            slo="client_observed_p99 < 1ms over 60s")
        c = StandaloneCluster(
            n_osds=3, pg_num=2, cephx=True, secret=os.urandom(32),
            profile="plugin=tpu_rs k=2 m=1",
            chunk_size=1024, op_timeout=6.0 * _lf())
        try:
            c.wait_for_clean(timeout=40 * _lf())
            engine = WorkloadEngine(c, [quiet, noisy], seed=11,
                                    duration_s=2.0)
            engine.setup()
            tagg = TelemetryAggregator()
            engine.run(tick=lambda: engine.ingest_clients(tagg),
                       tick_interval=0.4)
            results = engine.results()
            # every tenant completed ops and owns percentiles
            for name in ("quiet", "noisy"):
                assert results[name]["ops"] > 0, results[name]
                assert "p99_ms" in results[name]
            # replay contract: the executed streams regenerate
            # bit-exactly from (profile, seed) alone
            for p in (quiet, noisy):
                fresh = OpStream.digest(
                    OpStream(p, 11).generate(2.0))
                assert fresh == results[p.name]["digest"]
            # the noisy tenant was visibly LIMIT-BOUND: its mClock
            # class's throttle counter moved on the OSDs
            fold = engine.fold_tenant_mclock(c)
            assert fold["client.noisy"]["throttled"] > 0, fold
            assert fold["client.noisy"]["profile"]["limit"] == 10.0
            # ...while the quiet tenant's own SLO verdict stays green
            rules = parse_slo_rules(engine.slo_rule_text())
            verdicts = tagg.slo_status(rules=rules)
            by_tenant = {v["tenant"]: v for v in verdicts}
            assert not by_tenant["client.quiet"]["breach"]
            assert by_tenant["client.quiet"]["intervals"] > 0
            # the quiet tenant's latency ring is populated under its
            # own label (the per-tenant feed the rule evaluated)
            tl = tagg.tenant_latency()
            assert tl["client.quiet"]["count"] > 0
        finally:
            c.shutdown()


@pytest.mark.slow
class TestWorkloadBenchLive:
    """Heavy cell (slow; the committed-artifact pin in
    test_bench_schema.py is the tier-1 representative): a full
    workload_bench run — 4-tenant builtin mix, daemon kill mid-run —
    emits the workload_r20/1 schema with the acceptance block."""

    def test_bench_json_schema(self, capsys, tmp_path):
        import json

        from tools import workload_bench
        out_path = tmp_path / "wl.json"
        workload_bench.main([
            "--duration", "4", "--seed", "3",
            "--num-osds", "4", "--pg-num", "2",
            "--profile", "plugin=tpu_rs k=2 m=1",
            "--chunk-size", "2048", "--json",
            "--out", str(out_path)])
        out = json.loads(capsys.readouterr().out)
        assert out["schema"] == "workload_r20/1"
        assert set(out["tenants"]) == set(BUILTIN_PROFILES)
        acc = out["acceptance"]
        assert acc["noisy_visibly_throttled"] is True
        assert acc["replay_digest_match"] is True
        assert acc["every_tenant_completed_ops"] is True
        assert acc["daemon_killed"] is True
        # the artifact on disk matches the stdout claim
        disk = json.loads(out_path.read_text())
        assert disk["acceptance"] == acc
        # --repro over the fresh artifact verifies bit-exactly
        with pytest.raises(SystemExit) as ei:
            workload_bench.main(["--repro", str(out_path)])
        assert ei.value.code == 0
