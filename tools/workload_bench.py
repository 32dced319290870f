"""workload bench — replayable multi-tenant traffic under faults (r20).

Drives N tenants — each its own cephx entity with its own declarative
traffic profile (op-size mix, read/write ratio, temporal phases,
hotspots, QoS class) — against a LIVE StandaloneCluster (real
sockets, cephx auth, AES-GCM secure frames), with a daemon kill +
recovery landing mid-run. Small overwrites route through the r16
write_at/append fast path, streaming writes through full stripes.

Op streams are generated up front from (profile, seed) alone and
committed with sha256 digests, so the artifact replays bit-exactly:

  python tools/workload_bench.py --duration 6 --seed 7 --json
  python tools/workload_bench.py --repro WORKLOAD_r20.json

The JSON carries per-tenant SLO verdicts (tenant-qualified r18
rules), per-tenant mClock grant/throttle attribution (who the
cluster is holding back, by name), routed-op and wire-amplification
counters, and the r18 telemetry block. The committed acceptance
claim: the noisy neighbor is visibly THROTTLED by its mClock class
(its own SLO allowed to burn) while every other tenant's p99 SLO
verdict stays green across the kill.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_profiles(spec: str):
    """--profiles value -> validated profiles: inline JSON, a JSON
    file path, or a comma list of builtin names."""
    from ceph_tpu.workload import builtin_mix, parse_profiles
    s = spec.strip()
    if s.startswith("[") or s.startswith("{"):
        return parse_profiles(s)
    if os.path.exists(s):
        with open(s) as f:
            return parse_profiles(f.read())
    return builtin_mix([t.strip() for t in s.split(",") if t.strip()])


def repro_check(path: str) -> int:
    """Replay contract check: regenerate every tenant's op stream
    from the committed artifact's profiles + seed and compare the
    sha256 digests bit-for-bit."""
    from ceph_tpu.workload import OpStream, parse_profiles
    with open(path) as f:
        data = json.load(f)
    profiles = parse_profiles(data["profiles"])
    seed = int(data["config"]["seed"])
    duration = float(data["config"]["duration_s"])
    ok = True
    for p in profiles:
        want = data["streams"][p.name]["digest"]
        got = OpStream.digest(OpStream(p, seed).generate(duration))
        match = got == want
        ok = ok and match
        print(f"  {p.name:>12}: {'MATCH' if match else 'MISMATCH'} "
              f"({got[:16]}...)")
    print(f"repro: {'ok — streams replay bit-exactly' if ok else 'FAILED'}")
    return 0 if ok else 1


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profiles",
                    default="interactive,streaming,bursty,noisy",
                    help="builtin names (comma list), inline JSON, "
                         "or a JSON file of tenant profiles")
    ap.add_argument("--num-osds", type=int, default=6)
    ap.add_argument("--pg-num", type=int, default=4)
    ap.add_argument("--profile",
                    default="plugin=tpu_rs k=4 m=2")
    ap.add_argument("--chunk-size", type=int, default=4096)
    ap.add_argument("--history-interval", type=float, default=0.5)
    ap.add_argument("--no-kill", action="store_true",
                    help="skip the mid-run daemon kill (the committed "
                         "run keeps it ON: recovery runs concurrently "
                         "with tenant traffic)")
    ap.add_argument("--insecure", action="store_true",
                    help="crc frames, no cephx (debug only; the "
                         "committed config keeps security ON)")
    ap.add_argument("--amp-ops", type=int, default=8,
                    help="fixed-count write_at cell for the committed "
                         "amplification A/B (small overwrite vs "
                         "full-stripe rewrite, same run)")
    ap.add_argument("--amp-size", type=int, default=1024)
    ap.add_argument("--repro", default=None,
                    help="path to a committed WORKLOAD JSON: verify "
                         "its op streams regenerate bit-exactly, "
                         "then exit")
    ap.add_argument("--out", default=None,
                    help="also write the JSON artifact to this path")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.repro is not None:
        raise SystemExit(repro_check(args.repro))
    if args.duration <= 0:
        raise SystemExit("workload_bench: --duration must be > 0")

    from ceph_tpu.utils.jax_cache import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    try:
        from ceph_tpu import native as _native
        _native.build()
    except Exception:   # noqa: BLE001 — no compiler: jax paths serve
        pass

    import numpy as np

    from ceph_tpu.mgr.telemetry import (TelemetryAggregator,
                                        parse_slo_rules)
    from ceph_tpu.osd.standalone import StandaloneCluster
    from ceph_tpu.workload import OpStream, WorkloadEngine, percentiles

    try:
        profiles = load_profiles(args.profiles)
    except ValueError as e:
        raise SystemExit(f"workload_bench: --profiles: {e}")

    try:
        c = StandaloneCluster(
            n_osds=args.num_osds, pg_num=args.pg_num,
            profile=args.profile, chunk_size=args.chunk_size,
            secret=None if args.insecure else os.urandom(32),
            cephx=not args.insecure,
            op_timeout=3.0, op_window=8)
    except ValueError as e:
        raise SystemExit(f"workload_bench: {e}")
    c.wait_for_clean(timeout=30)
    admin = c.client()
    admin.config_set("mgr_history_interval", args.history_interval)
    # fast report cadence so the mon-side per-tenant aggregate
    # (`ceph_cli top`) has fresh mClock claims inside a short run
    admin.config_set("mgr_report_interval",
                     max(0.25, args.history_interval / 2))

    def _osd_perf(d):
        return d.perf_dump_all() if hasattr(d, "perf_dump_all") \
            else d.asok("perf dump")

    def ec_totals():
        tot: dict = {}
        for d in c.osds.values():
            if d._stop.is_set():
                continue
            for key, v in _osd_perf(d).get("ec", {}).items():
                if isinstance(v, (int, float)):
                    tot[key] = tot.get(key, 0) + v
        return tot

    # -- block-path amplification A/B (deterministic counts) ------------------
    # The satellite-1 measurement, wire tier, committed in THIS
    # artifact: bytes-on-wire to land one small overwrite via the
    # write_at fast path vs via a full-stripe rewrite of the same
    # object, pure counter deltas, same run.
    rng = np.random.default_rng(args.seed)
    prof_kv = dict(tok.split("=", 1) for tok in args.profile.split()
                   if "=" in tok)
    prof_k = int(prof_kv.get("k", 4))
    amp_obj_size = prof_k * args.chunk_size     # exactly one stripe
    amp_names = [f"amp-{j}" for j in range(4)]
    for nm in amp_names:
        admin.write({nm: rng.integers(0, 256, amp_obj_size,
                                      np.uint8).tobytes()})
    admin.write_at(amp_names[0], 0,               # warm (jit outside)
                   rng.integers(0, 256, args.amp_size,
                                np.uint8).tobytes())
    ec0 = ec_totals()
    for nm in amp_names:
        admin.write({nm: rng.integers(0, 256, amp_obj_size,
                                      np.uint8).tobytes()})
    ec1 = ec_totals()
    full_wire = ec1.get("write_wire_bytes", 0) \
        - ec0.get("write_wire_bytes", 0)
    ec2 = ec_totals()
    for i in range(args.amp_ops):
        nm = amp_names[i % len(amp_names)]
        col = i % prof_k
        span = max(1, args.chunk_size - args.amp_size + 1)
        off = col * args.chunk_size + (i * 512) % span
        admin.write_at(nm, off, rng.integers(
            0, 256, args.amp_size, np.uint8).tobytes())
    ec3 = ec_totals()

    def delta(key):
        return ec3.get(key, 0) - ec2.get(key, 0)
    rmw_wire = delta("rmw_wire_bytes")
    rmw_per_op = rmw_wire / max(1, args.amp_ops)
    full_per_op = full_wire / max(1, len(amp_names))
    amplification = {
        "overwrite_size": args.amp_size,
        "object_size": amp_obj_size,
        "write_at": {
            "ops": args.amp_ops,
            "rmw_ops": delta("rmw_ops"),
            "wire_bytes": rmw_wire,
            "wire_bytes_per_op": round(rmw_per_op, 1),
            "preread_bytes": delta("rmw_preread_bytes"),
            "append_fast_ops": delta("rmw_append_fast"),
            "full_fallbacks": delta("rmw_full_fallbacks"),
        },
        "full_stripe_baseline": {
            "ops": len(amp_names),
            "wire_bytes": full_wire,
            "wire_bytes_per_op": round(full_per_op, 1),
        },
        "ratio_vs_full_stripe": round(
            rmw_per_op / max(1e-9, full_per_op), 6),
    }

    # -- the tenant run -------------------------------------------------------
    engine = WorkloadEngine(c, profiles, seed=args.seed,
                            duration_s=args.duration)
    engine.setup()
    try:
        rules = parse_slo_rules(engine.slo_rule_text())
    except ValueError as e:
        raise SystemExit(f"workload_bench: profile slo: {e}")
    tagg = TelemetryAggregator()

    killed = {"at": None, "victim": None}

    def kill_one():
        # a pure shard holder, not a primary: recovery then COMPETES
        # with tenant traffic through mClock (the QoS-under-faults
        # scenario); a primary victim would measure the detection
        # window instead
        primaries = {
            admin.osdmap.pg_to_up_acting_osds(1, ps)[2][0]
            for ps in range(args.pg_num)}
        live = [o for o in c.osd_ids()
                if not c.osds[o]._stop.is_set()]
        pool = [o for o in live if o not in primaries] or live
        victim = max(pool)
        c.kill_osd(victim)
        killed["at"] = time.perf_counter()
        killed["victim"] = victim

    killer = None
    if not args.no_kill:
        killer = threading.Timer(args.duration / 3.0, kill_one)
        killer.daemon = True
        killer.start()
    engine.run(tick=lambda: engine.ingest_clients(tagg),
               tick_interval=args.history_interval)
    if killer is not None:
        killer.cancel()

    # -- attribution read-back ------------------------------------------------
    for d in c.osds.values():
        if d._stop.is_set():
            continue
        try:
            if hasattr(d, "metrics_history"):
                d.metrics_history.tick()
                hist = d.metrics_history.dump()
            else:
                hist = d.asok("perf history")
        except Exception:   # noqa: BLE001 — a dying daemon drops out
            continue
        tagg.ingest(d.name, hist.get("entries") or [])
    verdicts = tagg.slo_status(rules=rules)
    mclock = engine.fold_tenant_mclock(c)
    # the mon-side aggregate the satellite-2 `ceph_cli top` table
    # renders — same fold, served over the MgrReport pipe
    try:
        mon_tenants = admin.mon_command("top").get("tenants") or {}
    except (ConnectionError, OSError, RuntimeError, KeyError):
        mon_tenants = {}

    # r19 continuous-profiling block: the daemons' cumulative flame
    # profiles folded over the whole tenant run — the bench
    # self-attributes where CPU went while the tenants competed
    from ceph_tpu.utils.profiler import profile_block
    pdumps = []
    for d in c.osds.values():
        if d._stop.is_set():
            continue
        try:
            pdumps.append(d.profiler.dump() if hasattr(d, "profiler")
                          else d.asok("profile"))
        except Exception:   # noqa: BLE001 — a dying daemon drops out
            continue
    profile_blk = profile_block(pdumps)

    # r21 capacity block: the committed ladder view (`df`) + the
    # full-ladder counters, same schema as rados_bench's block
    # (pinned by tests/test_bench_schema.py) — all-zeros on an
    # unbounded run, the contract either way
    try:
        df = admin.mon_command("df")
    except Exception:   # noqa: BLE001 — a dying cluster still ships
        df = {}         # the block, flagged empty

    def _counter_total(key):
        tot = 0
        for d in c.osds.values():
            if d._stop.is_set():
                continue
            for counters in _osd_perf(d).values():
                if isinstance(counters, dict) \
                        and isinstance(counters.get(key),
                                       (int, float)):
                    tot += int(counters[key])
        return tot
    fb = admin.perf.dump().get("full_backoff_time") or {}
    capacity_blk = {
        "cluster_full": bool(df.get("cluster_full", False)),
        "full_ratios": df.get("full_ratios") or {},
        "total_bytes": int(df.get("total_bytes", 0)),
        "total_used_bytes": int(df.get("total_used_bytes", 0)),
        "osds": df.get("osds") or {},
        "pools": df.get("pools") or {},
        "writes_rejected_full":
            _counter_total("writes_rejected_full"),
        "client_full_backoff": {
            "count": int(fb.get("avgcount", 0)),
            "total_s": round(float(fb.get("sum", 0.0)), 3)},
    }

    results = engine.results(killed_at=killed["at"])
    noisy_names = [p.name for p in profiles if p.mclock]
    quiet_names = [p.name for p in profiles
                   if p.slo and not p.mclock]
    tenants_block = {}
    for p in profiles:
        row = dict(results[p.name])
        row["mclock"] = mclock.get(row["entity"]) or {}
        row["slo"] = [v for v in verdicts
                      if v.get("tenant") == row["entity"]]
        tenants_block[p.name] = row

    def _green(name):
        # non-vacuous green: the verdict must have evaluated at least
        # the fast-burn window's worth of data intervals — a ring too
        # sparse to breach doesn't count as "held its SLO"
        vs = tenants_block[name]["slo"]
        return bool(vs) and all(v["intervals"] >= 2
                                and not v["breach"] for v in vs)

    noisy_throttled = sum(
        tenants_block[n]["mclock"].get("throttled", 0)
        for n in noisy_names)
    acceptance = {
        "noisy_tenants": [tenants_block[n]["entity"]
                          for n in noisy_names],
        "noisy_throttled": noisy_throttled,
        "noisy_visibly_throttled": noisy_throttled > 0,
        "quiet_tenants_green": all(_green(n) for n in quiet_names),
        "every_tenant_completed_ops": all(
            r["ops"] > 0 for r in results.values()),
        "replay_digest_match": all(
            OpStream.digest(OpStream(p, args.seed)
                            .generate(args.duration))
            == results[p.name]["digest"] for p in profiles),
        "overwrite_wire_vs_full_stripe":
            amplification["ratio_vs_full_stripe"],
        "daemon_killed": killed["at"] is not None,
    }
    out = {
        "schema": "workload_r20/1",
        "config": {
            "seed": args.seed, "duration_s": args.duration,
            "elapsed_s": round(engine.elapsed, 3),
            "n_osds": args.num_osds, "pg_num": args.pg_num,
            "profile": args.profile, "chunk_size": args.chunk_size,
            "cephx": not args.insecure,
            "secure": not args.insecure,
            "history_interval": args.history_interval,
            "kill": not args.no_kill,
            "mclock_table": engine.mclock_tenant_table(),
            "slo_rules": engine.slo_rule_text(),
        },
        "profiles": [p.to_dict() for p in profiles],
        "streams": {p.name: {
            "ops": results[p.name]["stream_ops"],
            "digest": results[p.name]["digest"],
            "routed": results[p.name]["routed"],
        } for p in profiles},
        "tenants": tenants_block,
        "mclock": {"folded": mclock, "mgr_aggregate": mon_tenants},
        "slo": verdicts,
        "telemetry": {
            "interval_s": args.history_interval,
            "quantiles": {
                "osd.op_latency_hist":
                    tagg.quantiles("osd", "op_latency_hist"),
            },
            "tenant_latency": tagg.tenant_latency(),
        },
        "amplification": amplification,
        "capacity": capacity_blk,
        "profile_block": profile_blk,
        "recovery_kill": {
            "victim": killed["victim"],
            "victim_killed_at_s": round(
                killed["at"] - engine._t0, 3)
            if killed["at"] is not None else None,
            "op_errors": sum(r["errors"] for r in results.values()),
            "all_ops": percentiles(
                [v for st in engine.tenants.values()
                 for v in st.lat]),
        },
        "acceptance": acceptance,
    }
    c.shutdown()
    text = json.dumps(out, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(out, indent=1, sort_keys=True) + "\n")
    if args.json:
        print(text)
    else:
        for p in profiles:
            row = tenants_block[p.name]
            print(f"  {p.name:>12} [{row['klass']}] ops={row['ops']} "
                  f"err={row['errors']} p99={row.get('p99_ms')}ms "
                  f"throttled={row['mclock'].get('throttled', 0)} "
                  f"green={_green(p.name)}")
        print(f"  acceptance: {json.dumps(acceptance)}")


if __name__ == "__main__":
    main()
