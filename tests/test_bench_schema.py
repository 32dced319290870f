"""Bench JSON schema smoke (Round-11/12 CI satellite): the benches'
machine-readable outputs carry the counters the acceptance numbers
are parsed from — this pins those schemas (and the committed
SCALE_r12.json artifact) so a refactor can't silently drop a key CI
reads."""

import json
import os

import pytest

from tools import rados_bench

PCT_KEYS = {"p50_ms", "p95_ms", "p99_ms", "p999_ms", "max_ms"}
HEDGE_KEYS = {"hedge_issued", "hedge_wins", "hedge_losses",
              "hedge_cancelled", "degraded_dispatch",
              "degraded_served"}


REACTOR_KEYS = {"loops", "wakeups", "loop_lag_ms_avg",
                "writeq_flushes", "writeq_stalls"}

# r15 critical-path attribution block (both benches emit it; the
# categories are mgr/tracing.py CATEGORIES + total)
TRACE_KEYS = {"trace_id", "found", "daemons", "spans",
              "critical_path"}
TRACE_CP_KEYS = {"queue", "crypto", "encode", "store", "wire",
                 "other", "total"}

# r18 telemetry block (both benches emit it): interval series +
# merged lhist quantiles + SLO verdicts; rados_bench adds the
# observed-client-latency feed
TELEMETRY_KEYS = {"interval_s", "series", "quantiles", "slo"}

# r19 continuous-profiling block (rados/recovery/repair bench emit
# it): folded-stack flame summary + sampler overhead accounting
PROFILE_KEYS = {"daemons", "hz", "samples", "idle_samples",
                "categories", "category_share", "top_stacks",
                "sampler_overhead"}
# r22 network block (rados_bench + recovery_bench emit it): the
# mon's link matrix roll-up — threshold, bounded worst-first link
# rows, slow verdicts, and the cluster flow totals
NETWORK_KEYS = {"enabled", "threshold_ms", "links_total", "links",
                "slow", "flow_totals", "daemons_reporting"}
FLOW_TOTAL_KEYS = {"bytes_tx", "frames_tx", "bytes_rx", "frames_rx",
                   "stalls", "stall_time_s", "writeq_bytes",
                   "writeq_frames"}
LINK_ROW_KEYS = {"from", "to", "channel", "ewma_ms", "last_ms",
                 "min_ms", "max_ms", "count", "p50_ms", "p95_ms",
                 "p99_ms"}

# r21 capacity block (rados_bench + workload_bench emit it): the
# mon's df view at run end plus the two capacity-stall counters the
# acceptance numbers are read from (OSD failsafe rejections, client
# parked-write backoff)
CAPACITY_KEYS = {"cluster_full", "full_ratios", "total_bytes",
                 "total_used_bytes", "osds", "pools",
                 "writes_rejected_full", "client_full_backoff"}
RATIO_KEYS = {"nearfull", "backfillfull", "full", "failsafe"}


def _check_capacity_block(cap):
    assert set(cap) == CAPACITY_KEYS
    assert set(cap["full_ratios"]) == RATIO_KEYS
    assert set(cap["client_full_backoff"]) == {"count", "total_s"}
    assert isinstance(cap["cluster_full"], bool)
    assert isinstance(cap["writes_rejected_full"], int)
    for name, row in cap["osds"].items():
        assert {"total", "used", "avail", "ratio", "state"} \
            <= set(row), name


PROFILE_CATS = {"queue", "crypto", "encode", "store", "wire",
                "reactor", "other"}
QUANTILE_KEYS = {"p50_ms", "p95_ms", "p99_ms", "count"}
SLO_VERDICT_KEYS = {"name", "logger", "key", "quantile",
                    "threshold_ms", "window_s", "intervals",
                    "samples", "current_ms", "burn_fast",
                    "burn_slow", "breach"}
OCL_KEYS = {"source", "pool"} | QUANTILE_KEYS


def _check_network_block(net):
    assert NETWORK_KEYS <= set(net)
    assert isinstance(net["enabled"], bool)
    assert net["threshold_ms"] >= 0
    assert isinstance(net["links_total"], int)
    if net["flow_totals"]:
        assert FLOW_TOTAL_KEYS <= set(net["flow_totals"])
    for row in net["links"]:
        assert LINK_ROW_KEYS <= set(row)
        assert row["channel"] in {"hb", "store"}
        assert row["count"] >= 0 and row["ewma_ms"] >= 0


def _check_telemetry_block(tel, want_ocl=False):
    assert TELEMETRY_KEYS <= set(tel)
    for series in tel["series"].values():
        for pt in series:
            assert {"bucket", "t", "interval_s", "value"} <= set(pt)
    for q in tel["quantiles"].values():
        assert set(q) == QUANTILE_KEYS
    for v in tel["slo"]:
        assert SLO_VERDICT_KEYS <= set(v)
        assert isinstance(v["breach"], bool)
    if want_ocl:
        assert set(tel["observed_client_latency"]) == OCL_KEYS


def _check_profile_block(prof):
    assert PROFILE_KEYS <= set(prof)
    assert prof["daemons"]
    assert prof["hz"] > 0
    assert set(prof["categories"]) == PROFILE_CATS
    assert set(prof["category_share"]) == PROFILE_CATS
    for row in prof["top_stacks"]:
        assert {"category", "stack", "samples"} <= set(row)
        assert row["category"] in PROFILE_CATS
    ov = prof["sampler_overhead"]
    assert ov["busy_s"] >= 0 and ov["busy_share"] >= 0


def test_bench_r19_artifact_pinned():
    """The committed r19 continuous-profiling artifact: a live
    cephx+secure cluster assembles a flame from >= 3 daemons over the
    MgrReport pipe, `ceph_cli flame --speedscope` exports a valid
    document, profile_diff attributes the injected osd.op busy-spin
    to its own stack in the op-path category, and the interleaved
    ON/OFF guard holds the default-hz sampler at <= ~1.05x median
    pairwise slowdown."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r19.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "profile_r19/1"
    acc = data["acceptance"]
    assert acc["flame_daemons_reporting"] >= 3
    assert acc["speedscope_valid"] is True
    assert acc["burn_attributed_to_expected_category"] is True
    assert 0.95 <= acc["overhead_median_pairwise_slowdown"] <= 1.10
    burn = data["cells"]["burn_attribution"]
    assert burn["expected_category"] == "other"
    assert burn["burn_mover"]["category"] == "other"
    assert burn["burn_mover"]["delta_share"] > 0
    assert "_one_client_op" in burn["burn_mover"]["stack"]
    guard = data["cells"]["overhead_guard"]
    assert len(guard["pairs"]) >= 6
    assert all(p["on"] > 0 and p["off"] > 0 for p in guard["pairs"])
    assert set(data["cells"]["flame_assembly"]["categories"]) \
        == PROFILE_CATS


def test_bench_r21_artifact_pinned():
    """The committed r21 capacity-exhaustion artifact (generated by
    tools/capacity_bench.py): a live cephx+secure cluster driven FULL
    mid-write-window with ZERO surfaced client errors — writes park
    and drain exactly-once bit-exact, reads + the implicit-FULL_TRY
    delete keep serving; recovery into backfillfull targets parks
    (counted) while degraded reads serve; the REAL-capacity failsafe
    window bounces, parks and drains; and one-shot ENOSPC at every
    TinStore txn phase leaves the store fsck-clean across SIGKILL."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r21.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "capacity_r21/1"
    assert data["config"]["cephx"] and data["config"]["secure"]
    assert data["config"]["full_ratios"] == {
        "nearfull": 0.85, "backfillfull": 0.90,
        "full": 0.95, "failsafe": 0.97}
    acc = data["acceptance"]
    assert acc["client_op_errors"] == 0
    assert acc["reads_served_under_full"] > 0
    assert acc["delete_passed_under_full"] is True
    assert acc["parked_drained_fraction"] == 1.0
    assert acc["drained_bit_exact"] is True
    assert acc["recovery_parked_backfillfull"] > 0
    assert acc["degraded_reads_served_under_backfillfull"] > 0
    assert acc["failsafe_writes_rejected"] > 0
    assert acc["enospc_phases_covered"] == 6
    assert acc["enospc_all_fsck_clean"] is True
    fw = data["cells"]["full_window"]
    assert fw["writer_parked_during_window"] is True
    assert fw["parked_drained"] == fw["parked_writes"] > 0
    assert fw["full_backoff"]["count"] > 0
    assert fw["full_backoff"]["total_s"] > 0
    matrix = data["cells"]["enospc_matrix"]
    assert set(matrix) == {
        "txn.apply", "wal.append", "flush.segment-written",
        "flush.manifest-swapped", "compact.segments-written",
        "compact.manifest-swapped"}
    for phase, row in matrix.items():
        assert row["fired"] == 1, phase
        assert row["fsck_clean"] is True, phase
        assert row["acked_bit_exact_and_accepts_after"] is True, phase


def test_bench_r22_artifact_pinned():
    """The committed r22 network-observability artifact (generated by
    tools/netobs_bench.py): a one-way delay injected on one directed
    link of a live cephx+secure cluster flips OSD_SLOW_PING_TIME
    naming EXACTLY that link within two grace windows and clears
    after the heal; the r14 helper ranking reprices the degraded peer
    worst (net_helper_penalties pinned) and the mon link_cost feed
    separates the edges; and the whole plane ON holds wire write
    throughput at parity with OFF (median of >= 6 interleaved
    same-binary pairs inside the r15 noise envelope)."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r22.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "netobs_r22/1"
    assert data["config"]["cephx"] and data["config"]["secure"]
    acc = data["acceptance"]
    assert acc["flip_within_two_grace_windows"] is True
    assert acc["named_exact_link"] is True
    assert acc["cleared_after_heal"] is True
    assert acc["helper_repriced_counter_pinned"] is True
    assert 0.95 <= acc["overhead_median_pairwise"] <= 1.10
    ld = data["cells"]["link_degrade"]
    assert ld["degraded_link"].endswith("(hb)")
    assert ld["flip_s"] <= ld["flip_budget_s"]
    assert ld["named_exact_link"] is True and ld["detail"]
    assert all(ld["degraded_link"] in ln for ln in ld["detail"])
    assert ld["clear_s"] <= ld["clear_budget_s"]
    assert ld["slow_link_suspects"] >= 1
    ha = data["cells"]["helper_avoidance"]
    assert ha["degraded_priced_worst"] is True
    assert ha["net_helper_penalties_after"] \
        > ha["net_helper_penalties_before"]
    feed = ha["mon_link_cost_us"]
    assert feed["degraded_us"] > 10 * max(1, feed["healthy_us"])
    og = data["cells"]["overhead_guard"]
    assert len(og["pairs"]) >= 6
    assert all(p["on"] > 0 and p["off"] > 0 for p in og["pairs"])
    assert 0.95 <= og["median_pairwise_on_over_off"] <= 1.10


def test_bench_r18_artifact_pinned():
    """The committed r18 telemetry overhead-guard artifact: the
    history-ring + latency-histogram plane ON at defaults holds wire
    write MB/s and recovery obj/s at parity with OFF (median of >= 6
    interleaved same-binary pairs inside the r15 noise envelope)."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r18.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "telemetry_r18/1"
    for cell in ("wire_write", "recovery"):
        c = data["cells"][cell]
        assert len(c["pairs"]) >= 6
        assert all(p["on"] > 0 and p["off"] > 0 for p in c["pairs"])
        assert 0.95 <= c["median_pairwise_on_over_off"] <= 1.10
    acc = data["acceptance"]
    assert 0.95 <= acc["wire_write_median_pairwise"] <= 1.10
    assert 0.95 <= acc["recovery_median_pairwise"] <= 1.10


def test_slo_rule_schema_pinned():
    """The mgr_slo_rules grammar and the parsed-rule dict schema the
    `slo` mon command / bench verdicts render from."""
    from ceph_tpu.mgr.telemetry import parse_slo_rules
    rules = parse_slo_rules("client_read_p99 < 50ms over 5m")
    assert [r.to_dict() for r in rules] == [{
        "name": "client_read_p99", "logger": "osd",
        "key": "op_r_latency_hist", "quantile": 0.99,
        "threshold_ms": 50.0, "window_s": 300.0}]


def test_slo_rule_tenant_qualifier_pinned():
    """The r20 grammar extension: an optional `[tenant=...]` suffix
    scopes a client_observed rule to one tenant's own latency ring
    (the workload engine's per-tenant feed); the qualifier is only
    legal on the client_observed feed, and unqualified rules keep the
    exact pre-r20 dict shape (pinned above)."""
    import pytest

    from ceph_tpu.mgr.telemetry import parse_slo_rules
    rules = parse_slo_rules(
        "client_observed_p99 < 30ms over 2m [tenant=client.noisy]")
    assert [r.to_dict() for r in rules] == [{
        "name": "client_observed_p99[client.noisy]",
        "logger": "client", "key": "op_lat_hist", "quantile": 0.99,
        "threshold_ms": 30.0, "window_s": 120.0,
        "tenant": "client.noisy"}]
    with pytest.raises(ValueError, match="only applies"):
        parse_slo_rules("client_read_p99 < 30ms over 2m "
                        "[tenant=client.noisy]")


WL_TENANT_KEYS = {"entity", "klass", "stream_ops", "ops", "errors",
                  "routed", "digest", "mclock", "slo", "pre_kill",
                  "post_kill"}
WL_ROUTED_KEYS = {"read", "write_at", "append", "write_full"}


def test_workload_r20_artifact_pinned():
    """The committed r20 multi-tenant workload artifact: a live
    cephx+secure run of the 4-tenant builtin mix with a daemon kill
    mid-run. The acceptance floors: the noisy neighbor is visibly
    THROTTLED by its own mClock class (throttle counters > 0, its
    own SLO burning) while every other tenant's p99 SLO verdict
    stays green; the op streams replay bit-exactly from
    (profiles, seed); and the write_at block path ships less than
    half the full-stripe baseline's wire bytes per overwrite."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "WORKLOAD_r20.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "workload_r20/1"
    cfg = data["config"]
    assert cfg["cephx"] and cfg["secure"] and cfg["kill"]
    assert cfg["mclock_table"] and cfg["slo_rules"]
    assert set(data["tenants"]) == {"interactive", "streaming",
                                    "bursty", "noisy"}
    for name, row in data["tenants"].items():
        assert WL_TENANT_KEYS <= set(row), name
        assert row["ops"] > 0 and PCT_KEYS <= set(row)
        assert set(row["routed"]) == WL_ROUTED_KEYS
    # streams block: every digest is a sha256 the --repro path can
    # regenerate from the committed profiles + seed alone
    for name, srow in data["streams"].items():
        assert len(srow["digest"]) == 64 and srow["ops"] > 0, name
        assert srow["digest"] == data["tenants"][name]["digest"]
    # block-path routing did what the profiles declared
    assert data["tenants"]["interactive"]["routed"]["write_at"] > 0
    assert data["tenants"]["streaming"]["routed"]["write_full"] > 0
    assert data["tenants"]["bursty"]["routed"]["append"] > 0
    # the noisy neighbor: limit-bound by ITS class, SLO burning
    noisy = data["tenants"]["noisy"]
    assert noisy["mclock"]["throttled"] > 0
    assert noisy["mclock"]["profile"]["limit"] == 25.0
    assert any(v["breach"] for v in noisy["slo"])
    # every quiet tenant held its SLO, non-vacuously, across a kill
    for q in ("interactive", "streaming", "bursty"):
        vs = data["tenants"][q]["slo"]
        assert vs and all(v["intervals"] >= 2 and not v["breach"]
                          for v in vs), q
    # the mon-side per-tenant aggregate rode the MgrReport pipe
    assert "client.noisy" in data["mclock"]["mgr_aggregate"]
    assert data["mclock"]["mgr_aggregate"]["client.noisy"][
        "throttled"] > 0
    # amplification: the write_at cell stayed on the delta path and
    # beat the full-stripe baseline
    amp = data["amplification"]
    assert amp["write_at"]["rmw_ops"] > 0
    assert amp["write_at"]["full_fallbacks"] == 0
    # the r19 profiling plane attributed the run: folded flames from
    # the surviving daemons (the kill victim drops out of the block)
    pb = data["profile_block"]
    assert pb["samples"] > 0 and pb["daemons"]
    assert "category_share" in pb and pb["top_stacks"]
    acc = data["acceptance"]
    assert acc["noisy_visibly_throttled"] is True
    assert acc["noisy_throttled"] > 0
    assert acc["quiet_tenants_green"] is True
    assert acc["replay_digest_match"] is True
    assert acc["every_tenant_completed_ops"] is True
    assert acc["daemon_killed"] is True
    assert acc["overwrite_wire_vs_full_stripe"] <= 0.5
    assert data["recovery_kill"]["victim_killed_at_s"] > 0


def _check_trace_block(tr):
    assert TRACE_KEYS <= set(tr)
    assert tr["found"] is True
    assert tr["spans"] > 0
    assert set(tr["critical_path"]) == TRACE_CP_KEYS
    assert tr["critical_path"]["total"] > 0


def test_rados_bench_json_schema(capsys):
    # the 0.4 s window alone can finish ZERO ops under full-suite
    # load; the bench's min-ops guard (r16 deflake) keeps the window
    # open — load_factor-scaled — until every tenant owns an op, so
    # the percentile assertions below are never vacuous
    rados_bench.main([
        "seq", "--transport", "standalone", "--insecure",
        "--seconds", "0.4", "--object-size", "2048", "--batch", "2",
        "--num-osds", "4", "--pg-num", "2", "--op-shards", "2",
        "--profile", "plugin=tpu_rs k=2 m=1",
        "--tenants", "2", "--hedge-delay-ms", "30", "--min-ops", "2",
        "--json"])
    out = json.loads(capsys.readouterr().out)
    # core stats + tail percentiles
    assert PCT_KEYS <= set(out)
    assert out["objects"] > 0 and out["ops_per_s"] > 0
    # hedge/degraded aggregate: all keys present, ints
    assert set(out["hedge"]) == HEDGE_KEYS
    assert all(isinstance(v, int) for v in out["hedge"].values())
    # per-tenant sections: entity + ops + percentiles + own counters
    assert set(out["tenants"]) == {"tenant0", "tenant1"}
    for t in out["tenants"].values():
        assert t["ops"] > 0
        assert PCT_KEYS <= set(t)
        assert HEDGE_KEYS <= set(t["hedge"])
    assert out["config"]["tenants"] == 2
    assert out["config"]["hedge_delay_ms"] == 30.0
    # attribution rides along (the r9 discipline): perf deltas exist
    assert "osd_total" in out["perf_delta"]
    assert "client" in out["perf_delta"]
    # r13: sharded-OSD + reactor attribution — per-shard occupancy
    # per daemon (every shard key present, counts are ints) and the
    # reactor loop-lag block the acceptance numbers are read from
    assert out["config"]["op_shards"] == 2
    assert out["config"]["msgr_workers"] == 1
    assert out["config"]["osd_procs"] is False
    assert out["shards"], "per-shard occupancy missing"
    served_total = 0
    for osd_name, shards in out["shards"].items():
        assert set(shards) == {"shard_0", "shard_1"}, osd_name
        for row in shards.values():
            assert isinstance(row["served"], int)
            assert isinstance(row["queued"], int)
            served_total += row["served"]
    assert served_total > 0
    assert REACTOR_KEYS <= set(out["reactor"])
    assert out["reactor"]["loops"] > 0
    # r15: the forced-sample probe's critical-path attribution — one
    # assembled trace spanning the client and at least one OSD
    _check_trace_block(out["trace"])
    assert any(d.startswith("client.") for d in out["trace"]["daemons"])
    assert any(d.startswith("osd.") for d in out["trace"]["daemons"])
    # r18: the telemetry block — series/quantiles/SLO verdicts from
    # the daemons' history rings, plus the observed-client-latency
    # feed (client-shipped histograms in this in-process run)
    _check_telemetry_block(out["telemetry"], want_ocl=True)
    assert out["telemetry"]["quantiles"][
        "osd.op_latency_hist"]["count"] > 0
    assert out["telemetry"]["observed_client_latency"]["count"] > 0
    assert {r["name"] for r in out["telemetry"]["slo"]} \
        == {"client_read_p99", "client_write_p99"}
    assert out["config"]["telemetry_off"] is False
    # r19: the continuous-profiling block — every OSD's sampling ring
    # folded into the flame summary CI diffs with profile_diff
    _check_profile_block(out["profile"])
    assert len(out["profile"]["daemons"]) == 4
    assert out["profile"]["samples"] >= 0
    # r21: the capacity block — the mon's df view plus the two
    # capacity-stall counters; this clean unbounded run never
    # laddered, so both counters pin at zero (non-vacuously: the df
    # rode the MgrReport statfs pipe for all 4 OSDs)
    _check_capacity_block(out["capacity"])
    assert out["capacity"]["cluster_full"] is False
    assert len(out["capacity"]["osds"]) == 4
    assert out["capacity"]["writes_rejected_full"] == 0
    assert out["capacity"]["client_full_backoff"]["count"] == 0
    # r22: the network block — the mon's link matrix + cluster flow
    # roll-up off the MgrReport side-field; even this short window
    # gets at least one report cycle (the bench holds the cluster
    # open past min-ops), so the flow totals are never vacuous
    _check_network_block(out["network"])
    assert out["network"]["enabled"] is True
    assert out["network"]["daemons_reporting"] >= 1
    assert out["network"]["flow_totals"]["bytes_tx"] > 0
    assert out["config"]["netobs_off"] is False


def test_bench_r13_artifact_pinned():
    """The committed r13 wire-bench artifact: schema keys CI parses,
    interleaved-median protocol evidence, and the floors the numbers
    must not silently regress below when re-committed."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r13.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "wire_r13/1"
    base = data["baselines"]["r12_head_measured"]
    r13 = data["r13"]
    for series in (base["write"], base["seq"], r13["write_default"],
                   r13["write_op_shards2"], r13["seq_default"]):
        assert len(series["mb_per_s_runs"]) >= 2
        assert series["mb_per_s_median"] > 0
    # the committed claim: r13 write beats the measured interleaved
    # r12 baseline; seq stays within noise of it
    assert (r13["write_op_shards2"]["mb_per_s_median"]
            > base["write"]["mb_per_s_median"])
    assert (r13["seq_default"]["mb_per_s_median"]
            > 0.9 * base["seq"]["mb_per_s_median"])
    acc = data["acceptance"]
    assert acc["write_vs_measured_baseline"] >= 1.1
    # per-shard + reactor attribution rides the committed cells
    cell = data["cells"]["write_op_shards2"]
    assert cell["config"]["op_shards"] == 2
    assert cell["shards"] and cell["reactor"]["loops"] > 0
    # the multi-process cell is present and annotated for 1-core
    assert "write_osd_procs_1core" in r13
    assert data["cells"]["write_osd_procs"]["config"]["osd_procs"]


REPAIR_KEYS = {"family", "helper_count", "wire_fraction",
               "helper_bytes_on_wire", "rebuilt_bytes",
               "repair_bytes_on_wire_per_rebuilt_byte", "vs_full_k",
               "vs_full_shard_reads", "range_batches",
               "helper_set_histogram"}


def test_bench_r14_artifact_pinned():
    """The committed r14 repair-locality artifact: schema keys CI
    parses, the per-cell `repair` blocks recovery_bench emits, and
    the acceptance floors — LRC k8m4l4 single-shard repair bytes on
    the wire <= 0.55x the RS full-k baseline, Clay helper bytes
    <= 0.75x full-shard reads. The metric is a COUNT over the
    planner's helper reads, so the floors are deterministic."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r14.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "recovery_r14/1"
    for cell in ("rs_k8m4", "lrc_k8m4l4", "clay_k8m4"):
        rep = data["cells"][cell]["repair"]
        assert REPAIR_KEYS <= set(rep), cell
        assert rep["helper_bytes_on_wire"] > 0
        assert rep["repair_bytes_on_wire_per_rebuilt_byte"] > 0
    assert data["cells"]["rs_k8m4"]["repair"]["family"] == "mds"
    assert data["cells"]["lrc_k8m4l4"]["repair"]["family"] \
        == "lrc_local"
    clay = data["cells"]["clay_k8m4"]["repair"]
    assert clay["family"] == "clay_planes"
    assert clay["range_batches"] >= 1
    acc = data["acceptance"]
    assert acc["lrc_vs_rs_full_k"] <= 0.55
    assert acc["clay_vs_full_shard_reads"] <= 0.75
    # the full-k baseline really is k reads per rebuilt byte
    assert acc["rs_full_k_bytes_per_rebuilt_byte"] == 8.0


@pytest.mark.slow
def test_recovery_bench_json_schema_live():
    """Live run of the r14 bench surface (slow sweep cell; the
    committed-artifact pin above is the tier-1 representative):
    recovery_bench --json emits the `repair` block with a local-group
    LRC plan and the bytes-on-wire ratio below full-k."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "tools",
                      "recovery_bench.py"),
         "-P", "plugin=lrc", "-P", "k=4", "-P", "m=2", "-P", "l=3",
         "--objects", "4", "--size", "8192",
         "--json"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout)
    rep = data["repair"]
    assert REPAIR_KEYS <= set(rep)
    assert rep["family"] == "lrc_local"
    assert rep["vs_full_k"] < 1.0
    assert rep["helper_set_histogram"]["lrc_local"]
    # r15: the sampled recovery trace rides the same JSON
    _check_trace_block(data["trace"])
    assert data["trace"]["daemons"] == ["recovery_bench"]
    # r18: the telemetry block over the run's local history ring
    _check_telemetry_block(data["telemetry"])
    assert data["telemetry"]["quantiles"][
        "ec.recover_launch_time_hist"]["count"] > 0
    # r19: the bench's own sampling profile rides the same JSON
    _check_profile_block(data["profile"])
    assert data["profile"]["daemons"] == ["recovery_bench"]


RMW_KEYS = {"ops", "logical_bytes", "wire_bytes",
            "wire_bytes_per_logical_byte", "wire_bytes_per_op",
            "shard_ios", "shard_ios_per_op", "participants_expected",
            "preread_bytes", "append_fast_ops", "full_fallbacks",
            "journal_entries", "delta_launches"}
FULL_KEYS = {"logical_bytes", "wire_bytes",
             "wire_bytes_per_logical_byte", "wire_bytes_per_op"}


def test_bench_r16_artifact_pinned():
    """The committed r16 partial-stripe-write artifact: schema keys
    CI parses, the per-cell amplification blocks rados_bench emits,
    and the acceptance floors — for 4 KiB overwrites at k=8 m=3
    (4 MiB stripes, cephx+secure), bytes-on-wire per logical byte on
    the RMW path <= 0.25x the full-stripe-encode baseline measured
    in the same run, and exactly 1 data + m parity shards transact
    per op. Every metric is a COUNT, so the floors are
    deterministic."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r16.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "rmw_r16/1"
    for cname in ("overwrite_4k_k8m3", "append_4k_k8m3"):
        cell = data["cells"][cname]
        amp = cell["amplification"]
        assert RMW_KEYS <= set(amp["rmw"]), cname
        assert FULL_KEYS <= set(amp["full_stripe_baseline"]), cname
        assert amp["rmw"]["ops"] > 0
        assert amp["rmw"]["wire_bytes"] > 0
        assert cell["config"]["cephx"] and cell["config"]["secure"]
        assert cell["config"]["profile"] \
            == "plugin=tpu_rs k=8 m=3 impl=bitlinear"
        assert cell["config"]["chunk_size"] == 512 * 1024
        assert cell["config"]["overwrite_size"] == 4096
    acc = data["acceptance"]
    assert acc["overwrite_wire_vs_full_stripe"] <= 0.25
    assert acc["append_wire_vs_full_stripe"] <= 0.25
    # exactly 1 data + m parity shards move per RMW op, and the clean
    # overwrite cell never laddered to the full path
    assert acc["overwrite_shard_ios_per_op"] == 4.0
    assert acc["shard_ios_expected"] == 4
    assert acc["overwrite_full_fallbacks"] == 0
    # appends into stripe padding read no pre-image at all
    assert acc["append_preread_bytes"] == 0


@pytest.mark.slow
def test_rados_bench_overwrite_schema_live():
    """Live run of the r16 bench surface (slow sweep cell; the
    committed-artifact pin above is the tier-1 representative): the
    overwrite workload emits the amplification block, the RMW path
    beats the full-stripe baseline, and the shard-IO counter shows
    exactly 1 data + m parity participants."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "tools",
                      "rados_bench.py"),
         "overwrite", "--transport", "standalone", "--insecure",
         "--object-size", "65536", "--batch", "2", "--num-osds", "8",
         "--pg-num", "2", "--rmw-ops", "8", "--overwrite-size",
         "2048", "--chunk-size", "8192",
         "--profile", "plugin=tpu_rs k=4 m=2",
         "--json"],
        capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout)
    amp = data["amplification"]
    assert RMW_KEYS <= set(amp["rmw"])
    assert amp["rmw"]["ops"] == 8
    assert amp["rmw"]["shard_ios_per_op"] == 3.0   # 1 data + m=2
    assert amp["rmw"]["full_fallbacks"] == 0
    # r17 prepare coalescing: one fetch wave per delta group, frames
    # bounded by the participant count (vs 1+m getattrs + a pre-read
    # RTT per span before)
    assert amp["rmw"]["prepare_fetch_waves"] > 0
    assert amp["rmw"]["prepare_fetch_frames_per_op"] <= 3.0
    assert amp["ratio_vs_full_stripe"] < 1.0
    _check_trace_block(data["trace"])


STORM_PASS_KEYS = {"seed", "delay_s", "integrity", "pulses",
                   "revives_inside", "revives_inside_fraction",
                   "repair_bytes", "policy_counters", "verify"}
RACK_KEYS = {"downed_rack_osds", "pgs_touched", "lost_histogram",
             "stripes_at_m1", "exposure_pgid", "exposure_risk",
             "ratio_risk_vs_pgid"}


def test_bench_r17_artifact_pinned():
    """The committed r17 repair-policy storm artifact: schema keys CI
    parses and the acceptance floors — under a seeded transient-heavy
    kill/revive storm (>= 50% revives inside the window, cephx +
    secure), deferred repair moves <= 0.5x the eager baseline's
    repair bytes with ZERO data-loss/resurrection violations and
    every object bit-exact vs the full-decode oracle in BOTH
    integrity modes; under a simulated rack loss, cumulative
    stripe-time at m-1 with risk ordering <= 0.5x PG-id ordering.
    Every metric is a COUNT, so the floors are deterministic."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_r17.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "repair_r17/1"
    assert data["config"]["cephx"] and data["config"]["secure"]
    storm = data["cells"]["transient_storm"]
    for pname in ("eager", "deferred_host", "deferred_device"):
        p = storm[pname]
        assert STORM_PASS_KEYS <= set(p), pname
        assert p["verify"]["violations"] == 0
        assert p["verify"]["oracle_checked"] > 0
    assert storm["deferred_host"]["integrity"] == "host"
    assert storm["deferred_device"]["integrity"] == "device"
    # the same seeded schedule ran every pass, >= 50% inside
    assert storm["eager"]["seed"] == storm["deferred_host"]["seed"]
    assert storm["deferred_host"]["revives_inside_fraction"] >= 0.5
    # lazy repair engaged: stripes parked, inside revives cancelled
    # with zero-byte cursor re-checks
    for pname in ("deferred_host", "deferred_device"):
        pc = storm[pname]["policy_counters"]
        assert pc["repair_deferred_stripes"] > 0
        assert pc["repair_deferred_cancelled"] > 0
        assert pc["repair_cancel_noop"] > 0
        assert "repair_urgent_parked" not in pc     # invariant (b)
    assert RACK_KEYS <= set(data["cells"]["rack_loss"])
    assert data["cells"]["rack_loss"]["stripes_at_m1"] > 0
    acc = data["acceptance"]
    assert acc["deferred_vs_eager_repair_bytes"] <= 0.5
    assert acc["risk_vs_pgid_exposure"] <= 0.5
    assert acc["revives_inside_fraction"] >= 0.5
    assert acc["invariant_violations"] == 0
    assert acc["bit_exact_both_integrity_modes"] is True


CHURN_KEYS = {"events", "transient", "permanent", "confirmed",
              "cancelled", "urgent", "revives_inside",
              "revives_outside", "eager_bytes", "deferred_bytes",
              "catchup_bytes", "ratio_deferred_vs_eager", "config",
              "policy_counters"}


def test_scale_r17_repair_churn_pinned():
    """The committed 10k-OSD repair-churn day replay (r17): a day of
    transient+permanent failures at warehouse rates (arxiv 1309.0186
    shape: >= 90% transient, short downtimes) through the REAL
    RepairPolicy in virtual time. Floors: deferred repair prices at
    <= 0.5x the eager baseline, a majority of transient events
    cancel, and the no-delay control proves the model's two paths
    agree when the policy is off."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "SCALE_r17.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "scale_sim_r17/1"
    churn = data["cells"]["repair_churn_day"]
    control = data["cells"]["repair_churn_eager_control"]
    for cell in (churn, control):
        assert CHURN_KEYS <= set(cell)
    assert churn["config"]["osds"] == 10000
    assert churn["config"]["transient_fraction"] >= 0.9
    assert churn["config"]["osd_repair_delay_s"] > 0
    assert churn["policy_counters"]["repair_deferred_cancelled"] \
        == churn["cancelled"]
    acc = data["acceptance"]
    assert acc["deferred_vs_eager_bytes"] <= 0.5
    assert acc["cancelled_fraction"] >= 0.5
    assert acc["eager_control_ratio"] == 1.0


REBALANCE_KEYS = {"moves", "rounds", "candidates_scored",
                  "candidates_per_s", "score_elapsed_s", "elapsed_s",
                  "max_dev_before", "max_dev_after", "spread_before",
                  "spread_after", "budget", "budget_used", "converged"}


def test_scale_sim_schema_and_acceptance_pinned():
    """The committed 10k-OSD / 1M-PG scale-sim artifact (r12): schema
    keys the docs/CI parse, plus the acceptance floors — balancer
    candidate throughput, 2x-imbalance convergence under budget, and
    the delta-vs-full wire-cost bound for single-OSD churn."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "SCALE_r12.json")
    with open(path) as f:
        data = json.load(f)
    assert data["schema"] == "scale_sim_r12/1"
    main = data["cells"]["scale_main"]
    for k in ("osds", "pg_num", "initial_map_launch_s",
              "placements_per_s", "churn_single_osd", "expansion",
              "failure", "rebalance", "follower_epoch", "inc_steps"):
        assert k in main, k
    assert main["osds"] == 10000 and main["pg_num"] == 1 << 20
    assert REBALANCE_KEYS <= set(main["rebalance"])
    for k in ("convergence_s", "upmap_pgs", "fraction_moved"):
        assert k in main["rebalance"], k
    bal2x = data["cells"]["balancer_2x"]
    assert REBALANCE_KEYS <= set(bal2x)
    for k in ("load_before_min", "load_before_max",
              "budget_respected", "convergence_s"):
        assert k in bal2x, k
    acc = data["acceptance"]
    assert acc["candidates_per_s"] >= 100_000
    assert acc["balancer_2x_max_dev_after"] <= 1.0
    assert acc["balancer_2x_converged"]
    assert acc["balancer_2x_budget_respected"]
    assert acc["single_osd_inc_to_full_ratio"] <= 0.05
