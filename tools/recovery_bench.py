"""PG recovery benchmark — objects/s through the mini-ECBackend.

The harness for BASELINE.md metric #2 (ref dataflow:
src/osd/ECBackend.cc RecoveryOp/continue_recovery_op, throttled by
osd_recovery_max_active in the reference; here the batched pipeline IS
the throttle knob). Writes N objects through the EC write path, kills
shards, then times recover_shards end-to-end (helper reads -> batched
decode on device -> writeback + hinfo).

  python tools/recovery_bench.py -P k=8 -P m=3 --objects 256 \
      --size $((1<<20)) --lost 1 --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--parameter", "-P", action="append", default=[])
    ap.add_argument("--objects", type=int, default=128)
    ap.add_argument("--size", type=int, default=1 << 20)
    ap.add_argument("--lost", type=int, default=1)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--no-verify-hinfo", action="store_true")
    ap.add_argument("--warm", action="store_true",
                    help="run one recovery before the timed/traced one "
                         "so jit compiles are out of frame — the "
                         "steady-state pipeline (stage/launch/fetch "
                         "overlap) is what the trace then shows")
    ap.add_argument("--trace", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of the recovery "
                         "phase into DIR (view with tensorboard/xprof; "
                         "the recovery.{pull,stage,launch,fetch,"
                         "push} spans mark the pipeline stages)")
    ap.add_argument("--history-interval", type=float, default=0.25,
                    help="seconds per telemetry interval for the "
                         "run's local MetricsHistory ring (the JSON "
                         "`telemetry` block's series granularity)")
    ap.add_argument("--slo",
                    default="ec.recover_launch_time_hist_p99 < 5s "
                            "over 60s",
                    help="SLO rules evaluated into the `telemetry` "
                         "block (mgr_slo_rules grammar; explicit "
                         "<logger>.<key> feeds work)")
    ap.add_argument("--profile-hz", type=float, default=25.0,
                    help="r19 CPU sampler rate for the run's local "
                         "profiler (0 = off, the profiling overhead-"
                         "guard OFF arm; the JSON gains a `profile` "
                         "block when on)")
    ap.add_argument("--telemetry-off", action="store_true",
                    help="disable the r18 telemetry plane for this "
                         "run (no history ring, latency histograms "
                         "off process-wide) — the overhead-guard OFF "
                         "arm; the JSON then carries no telemetry "
                         "block")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    # persistent jit cache: the r09 cold number (4.4 obj/s vs 43.3
    # warm) WAS the compile — with the cache a cold process loads the
    # serialized executable instead
    from ceph_tpu.utils.jax_cache import enable_persistent_compile_cache
    cache_dir = enable_persistent_compile_cache()
    try:
        from ceph_tpu import native
        native.build()   # host-integrity CRCs want the SSE4.2 path
    except Exception:    # noqa: BLE001 — no compiler: jax CRCs serve
        pass

    from ceph_tpu.ec.interface import profile_from_string
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.osd.ecbackend import ECBackend, RecoveryRunner, ShardSet

    from ceph_tpu.osd.scheduler import MClockScheduler

    profile = profile_from_string(" ".join(args.parameter)) or {}
    profile.setdefault("k", "8")
    profile.setdefault("m", "3")
    try:
        cluster = ShardSet()
        # the coder owns the slot count (LRC interleaves local
        # parities into the position space, so n > k+m there)
        coder = factory(dict(profile))
        k, m = coder.get_data_chunk_count(), coder.get_coding_chunk_count()
        n_slots = coder.get_chunk_count()
        be = ECBackend(profile, "1.0", list(range(n_slots)), cluster)
        if args.lost > m:
            raise SystemExit(f"--lost {args.lost} exceeds m={m}")
    except ValueError as e:
        raise SystemExit(str(e))

    rng = np.random.default_rng(0)
    objs = {f"obj{i:06d}": rng.integers(0, 256, size=args.size,
                                        dtype=np.uint8)
            for i in range(args.objects)}
    t0 = time.perf_counter()
    be.write_objects(objs)
    t_write = time.perf_counter() - t0

    lost = list(range(args.lost))
    for s in lost:
        cluster.stores.pop(be.acting[s], None)
    repl = {s: 1000 + s for s in lost}

    if args.warm:
        # compile + rebuild once, then re-lose the shards so the
        # measured/traced recovery hits every jit cache
        be.recover_shards(lost, replacement_osds=repl,
                          batch=args.batch,
                          verify_hinfo=not args.no_verify_hinfo)
        for s in lost:
            cluster.stores.pop(be.acting[s], None)
        repl = {s: 2000 + s for s in lost}

    from ceph_tpu.utils.perf_counters import MetricsHistory, dump_delta
    from ceph_tpu.utils.tracing import span, trace
    if args.telemetry_off:
        import ceph_tpu.utils.perf_counters as _pcmod
        _pcmod.LHIST_ENABLED = False
    perf_before = be.perf.dump()
    # r18: a local per-interval history ring over the "ec" logger —
    # the in-process analog of a daemon's MetricsHistory, feeding the
    # JSON telemetry block (series + merged quantiles + SLO verdicts)
    hist = None
    if not args.telemetry_off:
        hist = MetricsHistory(lambda: {"ec": be.perf.dump()},
                              interval=args.history_interval)
        hist.tick()               # baseline snapshot
    # r19: no daemons here — the bench process carries its OWN
    # sampling profiler, so the recovery pipeline's CPU split
    # (encode vs store vs other) lands in the JSON like a daemon's
    prof = None
    if not args.telemetry_off and args.profile_hz > 0:
        from ceph_tpu.utils.profiler import SamplingProfiler
        prof = SamplingProfiler("recovery_bench",
                                hz=args.profile_hz).start()

    def timed_recover():
        """The timed phase runs through the SAME plan/runner/mClock
        pipeline the wire-tier OSD uses: plan -> scheduler grant ->
        runner.step per grant — so the emitted mClock occupancy and
        push-window stats are the real admission path's, not a
        simulation bolted on after."""
        sched = MClockScheduler()
        plan = be.plan_recovery(lost, replacement_osds=repl,
                                verify_hinfo=not args.no_verify_hinfo)
        runner = RecoveryRunner([plan], batch=args.batch,
                                perf=be.perf)
        more, queued = True, False
        while more:
            if not queued:
                sched.enqueue("background_recovery", runner,
                              cost=max(1.0,
                                       runner.next_cost() / (8 << 20)))
                queued = True
            got = sched.dequeue(time.monotonic())
            if got is None:          # limit-bound: the bench does not
                time.sleep(0.001)    # outrun the default QoS ceiling
                continue
            queued = False
            more = got[1].step()
            if hist is not None:
                hist.maybe_tick()    # close passed interval bounds
        runner.finish()
        return plan, runner, sched

    # r15: the timed recovery runs under a SAMPLED flight-recorder
    # context, so the recovery.* spans assemble into one
    # causal timeline with critical-path attribution — the same
    # instrumentation points feed the jax.profiler trace, the perf
    # counters, and this block (schema pinned by test_bench_schema)
    from ceph_tpu.utils.flight_recorder import (FlightRecorder,
                                                TraceContext, activate,
                                                new_trace_id)
    flight = FlightRecorder("recovery_bench")
    trace_ctx = TraceContext(new_trace_id(), 0, sampled=True)

    def traced_recover():
        with activate(trace_ctx, flight):
            with span("osd.recovery_round"):
                return timed_recover()

    t0 = time.perf_counter()
    if args.trace:
        # trace ONLY the recovery phase: the write-path compile noise
        # is out of frame, so the pipeline overlap (stage / launch /
        # fetch+writeback spans) is what the timeline shows
        with trace(args.trace) as traced:
            timed = traced_recover()
        if not traced:
            print("warning: jax.profiler unavailable, no trace "
                  "captured", file=sys.stderr)
    else:
        timed = traced_recover()
    t_rec = time.perf_counter() - t0
    counters = timed[0].counters

    import jax
    # repair-locality planner attribution (ROADMAP item 2's headline
    # metric): helper bytes pulled per rebuilt byte — a pure COUNT, so
    # it's deterministic and benchmarkable even on a loaded 1-core box.
    # vs_full_k normalizes against the MDS baseline (k full rows per
    # rebuilt row); vs_full_shard_reads against pulling this plan's
    # helper set WITHOUT sub-chunk ranges (the Clay wire saving).
    plan, runner, sched = timed
    wire = runner.stats["helper_bytes_on_wire"]
    rebuilt = max(1, counters["bytes"])
    rp = plan.repair
    histogram: dict = {}
    if rp is not None:
        histogram.setdefault(rp.family, {})
        histogram[rp.family][str(len(rp.helpers))] = \
            histogram[rp.family].get(str(len(rp.helpers)), 0) + 1
    repair_stats = {
        "family": rp.family if rp is not None else None,
        "helper_count": len(plan.helper),
        "wire_fraction": rp.wire_fraction if rp is not None else 1.0,
        "helper_bytes_on_wire": wire,
        "rebuilt_bytes": counters["bytes"],
        "repair_bytes_on_wire_per_rebuilt_byte":
            round(wire / rebuilt, 4),
        "vs_full_k": round(wire / rebuilt / max(1, k), 4),
        "vs_full_shard_reads": round(
            wire / max(1, len(plan.helper) * rebuilt
                       // max(1, len(plan.lost))), 4),
        "range_batches": runner.stats["range_batches"],
        "helper_set_histogram": histogram,
    }
    stats = {
        "plugin": profile.get("plugin", "tpu_rs"), "k": k, "m": m,
        "objects": args.objects, "object_size": args.size,
        "lost_shards": args.lost,
        "write_s": round(t_write, 3),
        "recover_s": round(t_rec, 3),
        "objects_per_s": round(args.objects / t_rec, 1),
        "recovered_MBps": round(counters["bytes"] / t_rec / 1e6, 1),
        "hinfo_failures": counters["hinfo_failures"],
        "repair": repair_stats,
        "backend": jax.default_backend(),
        "jax_compile_cache": cache_dir,
        # per-stage attribution over the timed recovery (the "ec"
        # logger's declared counters): launches, program-cache
        # hits, stage/launch/fetch/writeback time split
        "perf_delta": {"ec": dump_delta(perf_before,
                                        be.perf.dump())},
        # cross-PG runner internals: batch formation, host-crc mode,
        # windowed-push occupancy, stale skips
        "window": runner.stats,
        # mClock class occupancy/grants for the timed phase (the
        # admission layer the wire tier runs recovery under)
        "mclock": sched.dump(),
    }
    # r15 critical-path attribution over the recovery trace
    from ceph_tpu.mgr.tracing import TraceAssembler
    asm = TraceAssembler()
    asm.ingest(flight.dump()["spans"])
    tid = f"{trace_ctx.trace_id:016x}"
    rec_asm = asm.assemble(tid)
    stats["trace"] = {
        "trace_id": tid,
        "found": rec_asm["found"],
        "daemons": rec_asm["daemons"],
        "spans": len(rec_asm["spans"]),
        "critical_path": rec_asm["critical_path"],
    }
    # r18 telemetry block: the run's interval series + merged
    # quantiles + SLO verdicts from the local history ring (schema
    # pinned by tests/test_bench_schema.py)
    if hist is not None:
        from ceph_tpu.mgr.telemetry import (TelemetryAggregator,
                                            parse_slo_rules)
        hist.tick()                  # close the final interval
        tagg = TelemetryAggregator()
        tagg.ingest("recovery_bench", hist.dump()["entries"])
        try:
            rules = parse_slo_rules(args.slo)
        except ValueError as e:
            raise SystemExit(f"recovery_bench: --slo: {e}")
        stats["telemetry"] = {
            "interval_s": args.history_interval,
            "series": {
                "ec.recovered_bytes":
                    tagg.series("ec", "recovered_bytes"),
                "ec.recover_launches":
                    tagg.series("ec", "recover_launches"),
            },
            "quantiles": {
                "ec.recover_launch_time_hist":
                    tagg.quantiles("ec", "recover_launch_time_hist"),
                "ec.decode_time_hist":
                    tagg.quantiles("ec", "decode_time_hist"),
            },
            "slo": tagg.slo_status(rules=rules),
        }
    if prof is not None:
        # r19 profile block (schema pinned by test_bench_schema):
        # the run's own flame — stop FIRST so the dump is final
        from ceph_tpu.utils.profiler import profile_block
        prof.stop()
        stats["profile"] = profile_block([prof.dump()])
    # r22 network block — truthfully empty: this bench is hermetic
    # (no messenger, no heartbeats, no MgrReport pipe), so there is
    # no link matrix to claim. The schema is the contract either way
    # (pinned by tests/test_bench_schema.py); the wire-tier numbers
    # live in rados_bench's block and BENCH_r22.json.
    stats["network"] = {
        "enabled": False,
        "threshold_ms": 0.0,
        "links_total": 0,
        "links": [],
        "slow": [],
        "flow_totals": {},
        "daemons_reporting": 0,
        "note": "hermetic run: no wire tier, no link matrix",
    }
    if args.json:
        print(json.dumps(stats))
    else:
        for kk, v in stats.items():
            print(f"{kk}: {v}")


if __name__ == "__main__":
    main()
