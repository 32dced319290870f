"""rados bench — client-side write/read throughput harness.

Recreation of the reference's client bench (ref: src/tools/rados/
rados.cc `rados bench <seconds> write|seq` — N-second timed loop of
fixed-size object writes through librados, then sequential reads of
what was written; reports throughput, IOPS, and latency percentiles).

The cluster here is the hermetic SimCluster, so absolute numbers
measure the framework's host+device pipeline (encode + store apply per
op), not network storage — useful for regression tracking and for
comparing EC vs replicated pool overheads, stated as such in the
output.

  python tools/rados_bench.py --seconds 3 --object-size 65536 write
  python tools/rados_bench.py --seconds 2 --pool replicated seq
  python tools/rados_bench.py --profile "k=8 m=3 plugin=tpu_rs" write
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def percentiles(lat: list[float]) -> dict:
    if not lat:
        return {}
    a = np.sort(np.asarray(lat))
    pick = lambda q: float(a[min(len(a) - 1, int(q * len(a)))])  # noqa: E731
    return {"p50_ms": round(pick(0.50) * 1e3, 3),
            "p95_ms": round(pick(0.95) * 1e3, 3),
            "p99_ms": round(pick(0.99) * 1e3, 3),
            # tail-latency acceptance metric of the degraded-read work
            # (Round-11): meaningless below ~1000 samples, where it
            # degenerates to max — reported anyway, judged with count
            "p999_ms": round(pick(0.999) * 1e3, 3),
            "max_ms": round(float(a[-1]) * 1e3, 3)}


def hedge_counters(cl) -> dict:
    """One client's hedge/degraded accounting (the 'client' logger)."""
    return cl.perf.dump()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("workload",
                    choices=["write", "seq", "overwrite", "append"],
                    help="write: timed writes; seq: write a working "
                         "set, then timed sequential reads; "
                         "overwrite: stage objects, then FIXED-COUNT "
                         "partial overwrites through the RMW fast "
                         "path with deterministic amplification "
                         "counters (bytes-on-wire per logical byte, "
                         "shard IOs per op) vs a full-stripe-rewrite "
                         "baseline measured in the same run; append: "
                         "same counters for tail appends to stream "
                         "objects (the no-preread path)")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--min-ops", type=int, default=2,
                    help="timed workloads: extend the window (up to "
                         "~4x, scaled by host load) until at least "
                         "this many ops — and one per tenant — "
                         "completed; a fully-loaded CI host can "
                         "otherwise finish 0 ops in a short window "
                         "and the percentile blocks are vacuous")
    ap.add_argument("--rmw-ops", type=int, default=24,
                    help="overwrite/append: exact op count (the "
                         "amplification metrics are COUNTS, so the "
                         "cell is deterministic, not timed)")
    ap.add_argument("--overwrite-size", type=int, default=4096,
                    help="overwrite/append: logical bytes per RMW op")
    ap.add_argument("--chunk-size", type=int, default=4096,
                    help="EC chunk size (stripe = k * chunk); the "
                         "r16 artifact runs 512 KiB chunks = 4 MiB "
                         "stripes at k=8")
    ap.add_argument("--object-size", type=int, default=64 * 1024)
    ap.add_argument("--num-osds", type=int, default=12)
    ap.add_argument("--pg-num", type=int, default=8)
    ap.add_argument("--pool", choices=["ec", "replicated"], default="ec")
    ap.add_argument("--profile", default=None,
                    help="EC profile string (default k=4 m=2)")
    ap.add_argument("--batch", type=int, default=8,
                    help="objects per client op (batched writes are "
                         "the TPU-native unit of work)")
    ap.add_argument("--window", type=int, default=8,
                    help="standalone transport: client in-flight op "
                         "window cap (0 = uncapped; 1 restores "
                         "one-op-per-round-trip)")
    ap.add_argument("--insecure", action="store_true",
                    help="standalone transport: crc frames, no cephx "
                         "(measures the secure-mode delta; the "
                         "committed config keeps security ON)")
    ap.add_argument("--transport", choices=["sim", "standalone"],
                    default="sim",
                    help="sim: hermetic in-process SimCluster; "
                         "standalone: REAL socket daemons with cephx "
                         "auth + AES-GCM secure frames (the "
                         "qa/standalone analog — measures the wire "
                         "stack, ref: rados bench against a vstart "
                         "cluster)")
    ap.add_argument("--recovery-kill", action="store_true",
                    help="standalone: kill one OSD a third into the "
                         "window so recovery runs CONCURRENTLY with "
                         "client ops — reports pre/post-kill latency "
                         "splits and the mClock class occupancy. "
                         "write kills a pure shard holder (QoS-"
                         "bounded-p95 scenario); seq kills a PRIMARY "
                         "(the degraded-read fast-path scenario: "
                         "reads must keep flowing through hedged "
                         "shard requests, not wait for recovery)")
    ap.add_argument("--trace-sample-rate", type=float, default=None,
                    help="standalone: client_trace_sample_rate, "
                         "committed live (fraction of op frames "
                         "sampled for distributed tracing; < 0 "
                         "disables context stamping entirely — the "
                         "off-sample overhead-guard comparison knob; "
                         "default: leave the cluster default)")
    ap.add_argument("--hedge-delay-ms", type=float, default=None,
                    help="standalone: client hedged-read delay in ms, "
                         "committed live via client_hedge_delay_ms "
                         "(0 = auto from latency history, < 0 = off; "
                         "default: leave the cluster default)")
    ap.add_argument("--op-shards", type=int, default=1,
                    help="standalone: osd_op_num_shards — op-queue "
                         "shards per OSD daemon (ops hash by PG id; "
                         "per-PG ordering preserved, independent PGs "
                         "dispatch concurrently); the JSON gains "
                         "per-shard occupancy")
    ap.add_argument("--msgr-workers", type=int, default=1,
                    help="standalone: epoll reactor threads per "
                         "messenger (connections bind round-robin)")
    ap.add_argument("--osd-procs", action="store_true",
                    help="standalone: run every OSD daemon as its OWN "
                         "OS process (multi-core scale-out — the GIL "
                         "stops mattering; on a 1-core host expect "
                         "parity, not speedup). Implies --store tin "
                         "semantics for revive; shares the persistent "
                         "jit cache across children")
    ap.add_argument("--history-interval", type=float, default=1.0,
                    help="standalone: mgr_history_interval committed "
                         "for the run (seconds per telemetry "
                         "interval; small so a short window still "
                         "yields a series)")
    ap.add_argument("--slo", default="client_read_p99 < 1s over 30s;"
                                     "client_write_p99 < 1s over 30s",
                    help="standalone: SLO rules evaluated into the "
                         "JSON `telemetry` block (mgr_slo_rules "
                         "grammar)")
    ap.add_argument("--telemetry-off", action="store_true",
                    help="standalone: disable the r18 telemetry "
                         "plane for this run — history rings off "
                         "(mgr_history_interval 0) AND latency "
                         "histograms off (process-wide) — the "
                         "overhead-guard OFF arm; the JSON then "
                         "carries no telemetry block")
    ap.add_argument("--netobs-off", action="store_true",
                    help="standalone: disable the r22 network "
                         "observability plane for this run — "
                         "osd_network_observability false (no RTT "
                         "folds, no flow side-field, no link matrix) "
                         "— the netobs overhead-guard OFF arm; the "
                         "JSON `network` block then reads disabled")
    ap.add_argument("--profile-hz", type=float, default=None,
                    help="standalone: daemon_profile_hz committed for "
                         "the run (r19 CPU sampler rate; 0 = off, the "
                         "profiling overhead-guard OFF arm; default "
                         "leaves the config default). The JSON gains "
                         "a `profile` block when sampling is on")
    ap.add_argument("--tenants", type=int, default=1,
                    help="standalone: run ops round-robin across N "
                         "client entities (per-tenant mClock classes "
                         "on every OSD); the JSON gains per-tenant "
                         "latency percentiles + hedge win/loss counts")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.seconds <= 0 or args.object_size <= 0 or args.batch <= 0:
        raise SystemExit("rados_bench: --seconds/--object-size/--batch "
                         "must be positive")
    if args.tenants < 1:
        raise SystemExit("rados_bench: --tenants must be >= 1")
    if args.recovery_kill and args.transport != "standalone":
        raise SystemExit("rados_bench: --recovery-kill needs "
                         "--transport standalone")
    if args.workload in ("overwrite", "append"):
        if args.transport != "standalone":
            raise SystemExit("rados_bench: overwrite/append measure "
                             "the wire RMW path; use --transport "
                             "standalone")
        if args.pool != "ec":
            raise SystemExit("rados_bench: overwrite/append are the "
                             "EC parity-delta cells; --pool ec")
        if args.rmw_ops <= 0 or args.overwrite_size <= 0:
            raise SystemExit("rados_bench: --rmw-ops/--overwrite-size "
                             "must be positive")
    if (args.tenants > 1 or args.hedge_delay_ms is not None) \
            and args.transport != "standalone":
        raise SystemExit("rados_bench: --tenants/--hedge-delay-ms "
                         "need --transport standalone")
    if (args.op_shards != 1 or args.msgr_workers != 1
            or args.osd_procs) and args.transport != "standalone":
        raise SystemExit("rados_bench: --op-shards/--msgr-workers/"
                         "--osd-procs need --transport standalone")
    if args.netobs_off and args.transport != "standalone":
        raise SystemExit("rados_bench: --netobs-off needs "
                         "--transport standalone")
    if args.osd_procs and (args.tenants > 1 or args.recovery_kill):
        raise SystemExit("rados_bench: --osd-procs composes with the "
                         "plain write/seq workloads (tenant/recovery-"
                         "kill attribution reads daemon RAM)")

    # persistent jit cache: a cold bench process stops re-paying every
    # XLA compile (the r09 cold-recovery tax); native codecs build once
    from ceph_tpu.utils.jax_cache import enable_persistent_compile_cache
    jax_cache_dir = enable_persistent_compile_cache()
    try:
        from ceph_tpu import native as _native
        _native.build()
    except Exception:   # noqa: BLE001 — no compiler: jax paths serve
        pass

    profile = (args.profile or "plugin=tpu_rs k=4 m=2") \
        if args.pool == "ec" else "replicated size=3"
    shutdown = None
    if args.transport == "standalone":
        import os as _os

        from ceph_tpu.osd.standalone import StandaloneCluster
        try:
            c = StandaloneCluster(
                n_osds=args.num_osds, pg_num=args.pg_num,
                profile=profile, chunk_size=args.chunk_size,
                secret=None if args.insecure else _os.urandom(32),
                cephx=not args.insecure,
                # 3s (the test tier's value), not 15: a dead shard
                # holder stalls the unlucky in-flight fan-out for ONE
                # rpc timeout before the suspect-marked degraded retry
                # — at 15s that single stall eats a whole bench window.
                # The fixed-count RMW cells are the exception: nothing
                # is killed there, and a timeout-retried 4 MiB staging
                # write would double-count ops in the deterministic
                # amplification counters
                op_timeout=30.0 if args.workload in ("overwrite",
                                                     "append")
                else 3.0,
                op_window=args.window,
                op_shards=args.op_shards,
                msgr_workers=args.msgr_workers,
                osd_procs=args.osd_procs,
                store="tin" if args.osd_procs else "mem")
        except ValueError as e:
            raise SystemExit(f"rados_bench: {e}")
        c.wait_for_clean(timeout=30)
        shutdown = c.shutdown
        wire_client = c.client()
        # r18 telemetry plane: small history intervals so even a
        # sub-second window yields a series; --telemetry-off is the
        # overhead-guard OFF arm (ring ticks off, histograms off)
        if args.telemetry_off:
            import ceph_tpu.utils.perf_counters as _pcmod
            _pcmod.LHIST_ENABLED = False
            wire_client.config_set("mgr_history_interval", 0)
            # the OFF arm silences the whole observability plane,
            # r19 CPU sampler included
            wire_client.config_set("daemon_profile_hz", 0)
        else:
            wire_client.config_set("mgr_history_interval",
                                   args.history_interval)
            if args.profile_hz is not None:
                wire_client.config_set("daemon_profile_hz",
                                       args.profile_hz)
        if args.netobs_off:
            # r22 overhead-guard OFF arm: no RTT folds on any daemon,
            # no network side-field in the MgrReports
            wire_client.config_set("osd_network_observability",
                                   "false")
        if args.hedge_delay_ms is not None:
            # committed centrally: every current AND future client of
            # this cluster resolves it live (the config-observer path)
            wire_client.config_set("client_hedge_delay_ms",
                                   args.hedge_delay_ms)
        if args.trace_sample_rate is not None:
            wire_client.config_set("client_trace_sample_rate",
                                   args.trace_sample_rate)
        # per-tenant clients: each is its own cephx entity (its own
        # messenger peer without cephx), so every OSD's mClock gives
        # it its own tenant class — the per-tenant QoS under test
        tenant_clients = [wire_client]
        tenant_entities = ["client.admin" if not args.insecure
                           else wire_client.msgr.name]
        for i in range(args.tenants - 1):
            if c.key_server is not None:
                ent = f"client.tenant{i}"
                sec = c.create_entity(ent, caps={"mon": "allow r",
                                                 "osd": "allow rwx"})
                tenant_clients.append(c.client(entity=ent, secret=sec))
                tenant_entities.append(ent)
            else:
                tcl = c.client()
                tenant_clients.append(tcl)
                tenant_entities.append(tcl.msgr.name)

        class _WireOb:   # the Objecter-shaped slice the loops use
            @staticmethod
            def write(objs, tenant=0):
                tenant_clients[tenant % len(tenant_clients)].write(
                    {k: np.asarray(v, np.uint8).tobytes()
                     for k, v in objs.items()})

            @staticmethod
            def read(names, tenant=0):
                return tenant_clients[
                    tenant % len(tenant_clients)].read_many(names)
        ob = _WireOb()

        def _osd_perf(d):
            # in-process daemons dump directly; multi-process handles
            # answer over their admin socket (same declared counters)
            if hasattr(d, "perf_dump_all"):
                return d.perf_dump_all()
            return d.asok("perf dump")

        def perf_snapshot():
            """Perf dumps of every live daemon + the bench client —
            before/after deltas ship in the JSON so the bench carries
            its own per-stage attribution (msgr frames, op-window
            stalls, encode launches, cephx rounds, hedge wins)."""
            snap = {d.name: _osd_perf(d)
                    for d in c.osds.values() if not d._stop.is_set()}
            snap["client"] = {
                "rpc": wire_client.rpc.perf.dump(),
                "msgr": wire_client.msgr.perf.dump(),
                "hedge": wire_client.perf.dump()}
            return snap

        def ec_totals():
            """Summed `ec` logger counters over live daemons — the
            deterministic amplification inputs (counts, not timers)."""
            tot: dict = {}
            for d in c.osds.values():
                if d._stop.is_set():
                    continue
                for key, v in _osd_perf(d).get("ec", {}).items():
                    if isinstance(v, (int, float)):
                        tot[key] = tot.get(key, 0) + v
            return tot

        def shard_occupancy():
            """Per-OSD, per-shard grant counts (the hash-spread view):
            the acceptance artifact's per-shard occupancy."""
            out = {}
            for d in c.osds.values():
                if d._stop.is_set():
                    continue
                try:
                    dump = d.shard_dump() if hasattr(d, "shard_dump") \
                        else d.asok("dump_op_shards")
                except Exception:   # noqa: BLE001 — a dying daemon
                    continue        # drops out of the attribution
                out[d.name] = {
                    sh: {"served": sum(r["served"]
                                       for r in classes.values()),
                         "queued": sum(r["queued"]
                                       for r in classes.values())}
                    for sh, classes in dump.items()}
            return out
    else:
        from ceph_tpu.client.rados import Rados
        from ceph_tpu.osd.cluster import SimCluster
        try:
            c = SimCluster(n_osds=args.num_osds, pg_num=args.pg_num,
                           profile=profile, chunk_size=4096)
        except ValueError as e:
            raise SystemExit(f"rados_bench: {e}")
        io = Rados(c).open_ioctx()

        class _SimOb:    # tenant-arg parity with the wire adapter
            @staticmethod
            def write(objs, tenant=0):
                io._ob.write(objs)

            @staticmethod
            def read(names, tenant=0):
                return io._ob.read(names)
        ob = _SimOb()

        def perf_snapshot():
            return {"cluster": c.perf.dump(),
                    "objecter": io._ob.perf.dump()}
    rng = np.random.default_rng(0)

    def batch(i):
        return {f"bench-{i}-{j}": rng.integers(
            0, 256, args.object_size, np.uint8)
            for j in range(args.batch)}

    def warm_buckets(write_fn, read_fn=None):
        """Compile every bucketed launch shape INSIDE warmup: random
        scatter alone can leave a power-of-two bucket cold, and one
        XLA compile mid-window (~1.5 s on a 1-core CPU host) wrecks
        the percentiles. Uses names that all hash to one PG so group
        sizes 1/2/4/batch are hit deterministically."""
        if args.transport != "standalone":
            return
        same_pg, i = [], 0
        while len(same_pg) < args.batch and i < 10000:
            nm = f"warmpg-{i}"
            i += 1
            if wire_client.osdmap.object_to_pg(1, nm)[1] == 0:
                same_pg.append(nm)
        sizes = sorted({1, 2, 4, max(1, args.batch)})
        for s in sizes:
            write_fn({nm: rng.integers(0, 256, args.object_size,
                                       np.uint8)
                      for nm in same_pg[:s]})
        if read_fn is not None:
            for s in sizes:
                read_fn(same_pg[:s])

    lat: list[float] = []
    lat_stamp: list[float] = []   # completion time of each timed op
    lat_tenant: list[list[float]] = [[] for _ in range(args.tenants)]
    nobj = 0
    killed_at = None
    op_errors = 0
    amplification = None

    def window_open(t_end, hard_end):
        """The min-ops/extend-window guard: a short timed window on a
        fully-loaded host can complete ZERO ops, leaving the
        percentile blocks vacuous — keep the window open (up to the
        load-scaled hard cap) until --min-ops landed and every tenant
        owns at least one."""
        now = time.perf_counter()
        if now < t_end:
            return True
        if now >= hard_end:
            return False
        if len(lat) < max(1, args.min_ops):
            return True
        return args.tenants > 1 and any(not tl for tl in lat_tenant)

    def hard_cap(t_start):
        from ceph_tpu.chaos.thrasher import load_factor
        return t_start + args.seconds * (1.0 + 3.0 * load_factor())

    def maybe_kill(t_kill, want_primary: bool):
        """--recovery-kill victim selection: a pure shard holder for
        the write workload (QoS-vs-recovery), a PRIMARY for seq (the
        degraded-read scenario — reads must ride the fast path)."""
        nonlocal killed_at
        if not args.recovery_kill or killed_at is not None \
                or time.perf_counter() < t_kill:
            return
        wire_client = tenant_clients[0]
        primaries = {
            wire_client.osdmap.pg_to_up_acting_osds(1, ps)[2][0]
            for ps in range(args.pg_num)}
        live = [o for o in c.osd_ids()
                if not c.osds[o]._stop.is_set()]
        pool = [o for o in live
                if (o in primaries) == want_primary] or live
        victim = max(pool)
        c.kill_osd(victim)
        killed_at = time.perf_counter()

    if args.workload == "write":
        # jit compile outside the window: objects scatter over PGs in
        # per-PG sub-batches whose sizes bucket to powers of two —
        # warm every bucket deterministically, then a few full rounds
        for wi in range(3):
            ob.write(batch(f"warmup{wi}"))
        warm_buckets(ob.write)
        perf_before = perf_snapshot()
        t_start = time.perf_counter()
        t_end = t_start + args.seconds
        t_hard = hard_cap(t_start)
        t_kill = t_start + args.seconds / 3.0
        i = 0
        while window_open(t_end, t_hard):
            # kill a NON-PRIMARY (pure shard holder): every PG it
            # held a shard for starts an mClock-governed recovery
            # round that now COMPETES with this loop's ops. A
            # primary victim would measure the client's dead-peer
            # retry timeout (a different, detection-window story),
            # not the QoS of recovery-vs-client admission.
            maybe_kill(t_kill, want_primary=False)
            ti = i % args.tenants
            objs = batch(i)
            t0 = time.perf_counter()
            try:
                ob.write(objs, tenant=ti)
                dt = time.perf_counter() - t0
                lat.append(dt)
                lat_tenant[ti].append(dt)
                lat_stamp.append(time.perf_counter())
                nobj += len(objs)
            except (ConnectionError, OSError, RuntimeError, KeyError):
                if killed_at is None:
                    raise
                # op raced the failure window (old primary dead, map
                # not committed yet): real clusters retry; count it
                op_errors += 1
                if os.environ.get("RADOS_BENCH_DEBUG"):
                    import traceback
                    traceback.print_exc()
            i += 1
        # measured elapsed, not the nominal window: an op crossing the
        # deadline still counts its real time (keeps write comparable
        # to seq and the MB/s honest)
        dt = time.perf_counter() - t_start
    elif args.workload == "seq":
        # stage a working set, then timed sequential reads
        staged = {}
        for i in range(8):
            objs = batch(i)
            ob.write(objs)
            staged.update(objs)
        warm_buckets(ob.write, ob.read)
        names = sorted(staged)
        perf_before = perf_snapshot()
        t_start = t0_all = time.perf_counter()
        t_end = t0_all + args.seconds
        t_hard = hard_cap(t_start)
        t_kill = t0_all + args.seconds / 3.0
        k = 0
        while window_open(t_end, t_hard):
            # seq + --recovery-kill: kill a PRIMARY — the degraded-
            # read scenario. Reads must keep completing through
            # hedged shard requests + any-k decode, not wait out
            # detection/peering/recovery (acceptance: p99 within 2x
            # of pre-kill, from this JSON's pre/post split).
            maybe_kill(t_kill, want_primary=True)
            ti = k % args.tenants
            group = names[(k * args.batch) % len(names):]
            group = group[:args.batch] or names[:args.batch]
            t0 = time.perf_counter()
            try:
                got = ob.read(group, tenant=ti)
                dt = time.perf_counter() - t0
                lat.append(dt)
                lat_tenant[ti].append(dt)
                lat_stamp.append(time.perf_counter())
                nobj += len(got)
            except (ConnectionError, OSError, RuntimeError, KeyError):
                if killed_at is None:
                    raise
                op_errors += 1
                if os.environ.get("RADOS_BENCH_DEBUG"):
                    import traceback
                    traceback.print_exc()
            k += 1
        dt = time.perf_counter() - t0_all
    else:
        # overwrite / append: FIXED-COUNT RMW cells with count-metric
        # amplification — bytes-on-wire per logical byte written and
        # shard IOs per op are deterministic counters (the only
        # trustworthy headline on a loaded 1-core host; the r14
        # repair-metric discipline applied to the write path), with a
        # full-stripe-rewrite baseline measured in the SAME run.
        prof_kv = dict(tok.split("=", 1) for tok in profile.split()
                       if "=" in tok)
        prof_k = int(prof_kv.get("k", 4))
        prof_m = int(prof_kv.get("m", 2))
        chunk = args.object_size // prof_k if args.object_size \
            >= prof_k else args.chunk_size
        staged_names = [f"rmw-{j}" for j in range(args.batch)]
        for nm in staged_names:
            wire_client.write({nm: rng.integers(
                0, 256, args.object_size, np.uint8).tobytes()})
        # warm the delta programs / native handles outside the counted
        # window (one op per distinct touched column the loop uses)
        wire_client.write_at(staged_names[0], 0,
                             rng.integers(0, 256, args.overwrite_size,
                                          np.uint8).tobytes())
        # baseline: full-object rewrite = the full-stripe encode a
        # 4 KiB change costs WITHOUT the RMW path (k+m shards move)
        ec0 = ec_totals()
        for nm in staged_names:
            wire_client.write({nm: rng.integers(
                0, 256, args.object_size, np.uint8).tobytes()})
        ec1 = ec_totals()
        full_wire = ec1.get("write_wire_bytes", 0) \
            - ec0.get("write_wire_bytes", 0)
        full_logical = len(staged_names) * args.object_size
        # the RMW cell proper
        perf_before = perf_snapshot()
        ec2 = ec_totals()
        t_start = time.perf_counter()
        stream_i = 0
        for i in range(args.rmw_ops):
            nm = staged_names[i % len(staged_names)]
            t0 = time.perf_counter()
            if args.workload == "overwrite":
                # offset pinned inside ONE data column (deterministic
                # 1-data+m-parity shard IOs): column walks round-robin,
                # in-chunk offset strides without crossing the chunk
                col = i % prof_k
                span = max(1, chunk - args.overwrite_size + 1)
                in_chunk = (i * 8192) % span
                off = col * chunk + in_chunk
                wire_client.write_at(nm, off, rng.integers(
                    0, 256, args.overwrite_size, np.uint8).tobytes())
            else:
                sname = f"stream-{stream_i % max(1, args.batch)}"
                stream_i += 1
                wire_client.append(sname, rng.integers(
                    0, 256, args.overwrite_size, np.uint8).tobytes())
            dt0 = time.perf_counter() - t0
            lat.append(dt0)
            lat_tenant[0].append(dt0)
            lat_stamp.append(time.perf_counter())
            nobj += 1
        dt = time.perf_counter() - t_start
        ec3 = ec_totals()

        def delta(key):
            return ec3.get(key, 0) - ec2.get(key, 0)
        rmw_logical = args.rmw_ops * args.overwrite_size
        rmw_wire = delta("rmw_wire_bytes")
        rmw_per_byte = rmw_wire / max(1, rmw_logical)
        full_per_byte = full_wire / max(1, full_logical)
        # per-OP comparison: what ONE overwrite ships on the RMW path
        # vs what the full-stripe encode ships to land the same
        # logical bytes (one full rewrite per staged object above)
        rmw_per_op = rmw_wire / max(1, delta("rmw_ops"))
        full_per_op = full_wire / max(1, len(staged_names))
        amplification = {
            "rmw": {
                "ops": delta("rmw_ops"),
                "logical_bytes": rmw_logical,
                "wire_bytes": rmw_wire,
                "wire_bytes_per_logical_byte": round(rmw_per_byte, 4),
                "wire_bytes_per_op": round(rmw_per_op, 1),
                "shard_ios": delta("rmw_shard_ios"),
                "shard_ios_per_op": round(
                    delta("rmw_shard_ios")
                    / max(1, delta("rmw_ops")), 3),
                "participants_expected": 1 + prof_m,
                "preread_bytes": delta("rmw_preread_bytes"),
                "append_fast_ops": delta("rmw_append_fast"),
                "full_fallbacks": delta("rmw_full_fallbacks"),
                "journal_entries": delta("journal_entries"),
                "delta_launches": delta("rmw_delta_launches"),
                # r17 prepare coalescing: ONE overlapped fetch wave
                # per delta group (frames = participant shards), vs
                # the 1+m sequential getattrs + a read RTT per span
                # the r16 prepare paid per op
                "prepare_fetch_waves": delta("rmw_fetch_waves"),
                "prepare_fetch_frames": delta("rmw_fetch_frames"),
                "prepare_fetch_frames_per_op": round(
                    delta("rmw_fetch_frames")
                    / max(1, delta("rmw_ops")), 3),
            },
            "full_stripe_baseline": {
                "logical_bytes": full_logical,
                "wire_bytes": full_wire,
                "wire_bytes_per_logical_byte": round(
                    full_per_byte, 4),
                "wire_bytes_per_op": round(full_per_op, 1),
            },
            # the acceptance headline: bytes-on-wire to land one
            # overwrite's logical bytes through the RMW path vs
            # through a full-stripe encode, same run, pure counts
            "ratio_vs_full_stripe": round(
                rmw_per_op / max(1e-9, full_per_op), 6),
        }

    from ceph_tpu.utils.perf_counters import dump_delta
    perf_delta = dump_delta(perf_before, perf_snapshot())
    if args.transport == "standalone":
        # sum the per-OSD deltas per logger/key so the attribution is
        # one readable table (per-daemon detail is in the raw dumps)
        from ceph_tpu.mgr.reports import _normalized
        from ceph_tpu.utils.perf_counters import fold_delta
        osd_total: dict = {}
        for name, dump in perf_delta.items():
            if name.startswith("osd."):
                osd_total = fold_delta(osd_total, _normalized(dump))
        perf_delta = {"osd_total": osd_total,
                      "client": perf_delta.get("client", {})}
    total_bytes = nobj * args.object_size
    out = {
        "workload": args.workload, "pool": args.pool,
        "transport": args.transport,
        "object_size": args.object_size, "batch": args.batch,
        "seconds": round(dt, 3), "objects": nobj,
        "mb_per_s": round(total_bytes / dt / 1e6, 2),
        "ops_per_s": round(len(lat) / dt, 1),
        "objects_per_s": round(nobj / dt, 1),
        **percentiles(lat),
        # counter-delta attribution over the timed window (declared
        # PerfCounters only): every BENCH_* number carries its own
        # per-stage breakdown
        "perf_delta": perf_delta,
        # machine-readable run config, same shape bench.py commits in
        # wire_rados_bench["config"] — CI diffs the whole dict
        "config": {
            "transport": args.transport,
            "cephx": args.transport == "standalone"
            and not args.insecure,
            "secure": args.transport == "standalone"
            and not args.insecure,
            "object_size": args.object_size, "batch": args.batch,
            "window": args.window
            if args.transport == "standalone" else None,
            "n_osds": args.num_osds, "pg_num": args.pg_num,
            "pool": args.pool, "profile": profile,
        },
        "note": ("standalone wire cluster: real sockets, cephx auth, "
                 "AES-GCM secure frames — measures the messenger+EC "
                 "stack on localhost"
                 if args.transport == "standalone" else
                 "hermetic SimCluster: measures the framework "
                 "pipeline, not network storage"),
    }
    if jax_cache_dir is not None:
        out["config"]["jax_compile_cache"] = jax_cache_dir
    if amplification is not None:
        # r16: the partial-stripe write cell's count-metric block —
        # schema pinned by tests/test_bench_schema.py
        out["amplification"] = amplification
        out["config"]["rmw_ops"] = args.rmw_ops
        out["config"]["overwrite_size"] = args.overwrite_size
        out["config"]["chunk_size"] = args.chunk_size
    if args.transport == "standalone":
        # hedge/degraded accounting + per-tenant percentiles: the
        # degraded-read and per-tenant-QoS acceptance numbers, keyed
        # so CI can parse them (tier-1 smoke asserts this schema)
        out["config"]["tenants"] = args.tenants
        out["config"]["hedge_delay_ms"] = args.hedge_delay_ms
        out["config"]["trace_sample_rate"] = args.trace_sample_rate
        # r13 concurrency shape + its attribution: per-shard op-queue
        # occupancy and the reactors' loop-lag (time a loop spent out
        # of select — what concurrent connections wait on)
        out["config"]["op_shards"] = args.op_shards
        out["config"]["msgr_workers"] = args.msgr_workers
        out["config"]["osd_procs"] = args.osd_procs
        out["shards"] = shard_occupancy()
        msgr_d = perf_delta.get("osd_total", {}).get("msgr", {})

        def _avg_ms(key):
            row = msgr_d.get(key) or {}
            cnt = row.get("avgcount") or 0
            return round(1e3 * row.get("sum", 0.0) / cnt, 6) \
                if cnt else 0.0
        out["reactor"] = {
            "loops": msgr_d.get("reactor_loops", 0),
            "wakeups": msgr_d.get("reactor_wakeups", 0),
            "loop_lag_ms_avg": _avg_ms("reactor_stall_time"),
            "writeq_flushes": msgr_d.get("writeq_flushes", 0),
            "writeq_stalls": msgr_d.get("writeq_stalls", 0),
        }
        # r15: critical-path attribution block — run ONE forced-sample
        # probe op round AFTER the timed window (the window itself ran
        # at the default sample rate, so the MB/s numbers carry only
        # off-sample cost), assemble its trace from the in-process
        # flight rings (asok for --osd-procs children), and attach the
        # queue/crypto/encode/store/wire split. Schema pinned by
        # tests/test_bench_schema.py.
        from ceph_tpu.mgr.tracing import TraceAssembler
        wire_client.trace_sample_rate = 1.0
        probe = {f"traceprobe-{j}": rng.integers(
            0, 256, args.object_size, np.uint8).tobytes()
            for j in range(2)}
        try:
            wire_client.write(probe)
            wire_client.read_many(sorted(probe))
        except (ConnectionError, OSError, RuntimeError, KeyError):
            pass                   # a dying cluster: block says so
        asm = TraceAssembler()
        asm.ingest(wire_client.flight.dump()["spans"])
        for d in c.osds.values():
            if d._stop.is_set():
                continue
            try:
                dump = d.flight.dump() if hasattr(d, "flight") \
                    else d.asok("trace dump")
            except Exception:   # noqa: BLE001 — a dying daemon drops
                continue        # out of the attribution
            asm.ingest(dump["spans"])
        tid = f"{wire_client.last_trace_id:016x}"
        probe_asm = asm.assemble(tid)
        out["trace"] = {
            "trace_id": tid,
            "found": probe_asm["found"],
            "daemons": probe_asm["daemons"],
            "spans": len(probe_asm["spans"]),
            "critical_path": probe_asm["critical_path"],
        }
        agg = {k: 0 for k in ("hedge_issued", "hedge_wins",
                              "hedge_losses", "hedge_cancelled",
                              "degraded_dispatch", "degraded_served")}
        tenants = {}
        for i, (tcl, ent) in enumerate(zip(tenant_clients,
                                           tenant_entities)):
            hc = hedge_counters(tcl)
            for key in agg:
                agg[key] += int(hc.get(key, 0))
            tenants[f"tenant{i}"] = {
                "entity": ent,
                "ops": len(lat_tenant[i]),
                **percentiles(lat_tenant[i]),
                "hedge": hc}
        out["hedge"] = agg
        out["tenants"] = tenants
        # r21 capacity block: the monitors' committed ladder view
        # (`df` — per-OSD statfs claims + full-ratio states + pool
        # quota flags) and the full-ladder counters: OSD failsafe
        # bounces and the bench client's time parked in full-backoff.
        # An unbounded run reads all-zeros with cluster_full false —
        # the schema (pinned by tests/test_bench_schema.py) is the
        # contract either way.
        try:
            df = wire_client.mon_command("df")
        except Exception:   # noqa: BLE001 — a dying cluster still
            df = {}         # ships the block, flagged empty

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
        fb = wire_client.perf.dump().get("full_backoff_time") or {}
        out["capacity"] = {
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
        out["config"]["history_interval"] = args.history_interval
        out["config"]["telemetry_off"] = args.telemetry_off
        if not args.telemetry_off:
            # r18 telemetry block: interval series + merged
            # quantiles + the observed-client-latency feed + SLO
            # verdicts, assembled from the daemons' OWN history
            # rings (in-process directly, asok for --osd-procs
            # children) so a short window doesn't depend on the
            # MgrReport cadence. Schema pinned by
            # tests/test_bench_schema.py.
            from ceph_tpu.mgr.telemetry import (TelemetryAggregator,
                                                parse_slo_rules)
            tagg = TelemetryAggregator()
            for d in c.osds.values():
                if d._stop.is_set():
                    continue
                try:
                    if hasattr(d, "metrics_history"):
                        d.metrics_history.tick()   # close the tail
                        hist = d.metrics_history.dump()
                    else:
                        hist = d.asok("perf history")
                except Exception:  # noqa: BLE001 — a dying daemon
                    continue       # drops out of the block
                tagg.ingest(d.name, hist.get("entries") or [])
            for tcl in tenant_clients:
                tagg.ingest_client(tcl.msgr.name, tcl.perf.dump())
            try:
                rules = parse_slo_rules(args.slo)
            except ValueError as e:
                raise SystemExit(f"rados_bench: --slo: {e}")
            out["telemetry"] = {
                "interval_s": args.history_interval,
                "series": {
                    "osd.op": tagg.series("osd", "op"),
                    "osd.op_in_bytes":
                        tagg.series("osd", "op_in_bytes"),
                },
                "quantiles": {
                    "osd.op_latency_hist":
                        tagg.quantiles("osd", "op_latency_hist"),
                    "osd.subop_latency_hist":
                        tagg.quantiles("osd", "subop_latency_hist"),
                },
                "observed_client_latency":
                    tagg.observed_client_latency(),
                "slo": tagg.slo_status(rules=rules),
            }
        if not args.telemetry_off and (args.profile_hz is None
                                       or args.profile_hz > 0):
            # r19 profile block: the daemons' cumulative flame
            # profiles folded in-process (asok for --osd-procs
            # children), top stacks + category split + sampler
            # overhead. Schema pinned by tests/test_bench_schema.py.
            from ceph_tpu.utils.profiler import profile_block
            pdumps = []
            for d in c.osds.values():
                if d._stop.is_set():
                    continue
                try:
                    if hasattr(d, "profiler"):
                        pdumps.append(d.profiler.dump())
                    else:
                        pdumps.append(d.asok("profile"))
                except Exception:  # noqa: BLE001 — a dying daemon
                    continue       # drops out of the block
            out["profile"] = profile_block(pdumps)
        # r22 network block: the monitors' link matrix (per-link RTT
        # EWMAs/quantiles off the shipped lhists), slow-link verdicts
        # against the live threshold, and cluster flow totals. All
        # REAL aggregates from the MgrReport pipe — a short window can
        # legitimately show a sparse matrix (the claims ride the
        # report cadence); with --netobs-off the block says disabled
        # and the matrix is empty by construction. Schema pinned by
        # tests/test_bench_schema.py.
        out["config"]["netobs_off"] = args.netobs_off
        try:
            net = wire_client.mon_command("dump_osd_network")
        except Exception:   # noqa: BLE001 — a dying cluster still
            net = {}        # ships the block, flagged empty
        out["network"] = {
            "enabled": not args.netobs_off,
            "threshold_ms": float(net.get("threshold_ms", 0.0)),
            "links_total": int(net.get("links_total", 0)),
            "links": [
                {k: v for k, v in row.items()}
                for row in (net.get("links") or [])[:16]],
            "slow": net.get("slow") or [],
            "flow_totals": net.get("flow_totals") or {},
            "daemons_reporting": int(net.get("daemons_reporting", 0)),
        }
    if args.recovery_kill:
        # latency split around the kill + the schedulers' class grants:
        # the QoS claim ("client p95 bounded during recovery", seq:
        # "degraded p99 within 2x of pre-kill") is checkable from this
        # one JSON line; tenant mClock classes ride the dumps
        k = killed_at if killed_at is not None else t_end
        pre = [v for t, v in zip(lat_stamp, lat) if t < k]
        post = [v for t, v in zip(lat_stamp, lat) if t >= k]
        out["recovery_kill"] = {
            "victim_killed_at_s": round((killed_at or 0) - t_start, 3),
            "op_errors": op_errors,
            "pre_kill": percentiles(pre),
            "post_kill": percentiles(post),
            "mclock": {d.name: d.sched_dump()
                       for d in c.osds.values()
                       if not d._stop.is_set()},
        }
    if shutdown is not None:
        shutdown()
    if args.json:
        print(json.dumps(out))
    else:
        for key, v in out.items():
            print(f"  {key:>14}: {v}")


if __name__ == "__main__":
    main()
