"""repair_bench — the r17 repair-policy storm bench (BENCH_r17.json).

Two cells, both COUNT-metric so the numbers are deterministic on a
loaded 1-core box:

* **transient_storm** — a seeded kill/revive storm (>= 50% of revives
  inside the `osd_repair_delay` window) replayed over THREE fresh
  wire-tier clusters (cephx + secure frames on): once eager
  (delay=0, the pre-r17 behavior), once deferred with host-integrity
  recovery, once deferred with device-integrity recovery. The metric
  is cluster-wide repair bytes (fused decode rebuilds + helper wire
  pulls + backfill copy-backs). Acceptance: deferred moves <= 0.5x
  the eager bytes, with zero data-loss/resurrection violations and
  every object bit-exact against BOTH the client read-back and a
  full-decode oracle (decode forced around a live data shard) in
  both integrity modes.

* **rack_loss** — a simulated rack failure mapped through the real
  CRUSH hierarchy: every touched PG joins the rebuild queue, and
  cumulative stripe-time at m-1 (repairpolicy.exposure_units — work
  processed until each exposed stripe completes) is compared between
  risk order (the r17 default) and PG-id order (pre-r17).
  Acceptance: risk order <= 0.5x.

  JAX_PLATFORMS=cpu python tools/repair_bench.py --out BENCH_r17.json
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "repair_r17/1"

PROFILE = "plugin=tpu_rs k=2 m=3"
N_OSDS = 8
PG_NUM = 4
M = 3


def _repair_bytes(c) -> int:
    return sum(d.ec_perf.get("recovered_bytes")
               + d.ec_perf.get("recover_wire_bytes")
               + d.perf.get("move_bytes")
               for d in c.osds.values() if not d._stop.is_set())


def _policy_counters(c) -> dict:
    out: dict = {}
    for d in c.osds.values():
        if d._stop.is_set():
            continue
        for k, v in d.repair_policy.counters.items():
            if v:
                out[k] = out.get(k, 0) + v
    return out


def _verify(c, cl, objects) -> dict:
    """Data-safety audit after a storm: every acked object reads back
    bit-exact through the client (no loss), and a FULL-DECODE oracle
    re-derives each object with a live data shard excluded, forcing
    reconstruction through parity (the decode path the rebuilds also
    used). Returns counts; any mismatch is a violation."""
    violations = 0
    oracle_checked = 0
    for name, want in sorted(objects.items()):
        if cl.read(name) != want:
            violations += 1
    for d in c.osds.values():
        if d._stop.is_set():
            continue
        for ps, be in sorted(d.backends.items()):
            for name, want in sorted(objects.items()):
                if name not in be.object_sizes:
                    continue
                got = be.read_object(name,
                                     dead_osds={be.acting[0]})
                if bytes(np.asarray(got, np.uint8).tobytes()) != want:
                    violations += 1
                oracle_checked += 1
    return {"violations": violations, "oracle_checked": oracle_checked}


def run_storm(seed: int, delay: float, integrity: str,
              pulses: int, load: float, log=print) -> dict:
    """One storm pass on a fresh cephx+secure cluster. The kill/
    revive schedule is seed-deterministic; `delay` selects eager
    (0) or deferred; `integrity` pins osd_recovery_integrity."""
    from ceph_tpu.osd.standalone import StandaloneCluster
    rng = random.Random(seed)
    secret = bytes(rng.randrange(256) for _ in range(32))
    c = StandaloneCluster(n_osds=N_OSDS, profile=PROFILE,
                          pg_num=PG_NUM, cephx=True, secret=secret,
                          hb_interval=0.25, hb_grace=1.2 * load)
    try:
        cl = c.client()
        cl.config_set("osd_repair_delay", delay)
        cl.config_set("osd_recovery_integrity", integrity)
        objects = {f"storm-{i}": bytes(rng.randrange(256)
                                       for _ in range(700))
                   for i in range(16)}
        cl.write(objects)
        c.wait_for_clean(timeout=60 * load)
        b0 = _repair_bytes(c)
        win = max(delay, 6.0 * load)     # the schedule's unit window
        #                                  (eager runs the same wall
        #                                  schedule as deferred)
        inside = 0
        t0 = time.monotonic()
        for pulse in range(pulses):
            victim = rng.randrange(N_OSDS)
            is_inside = pulse % 4 != 3   # 3 of 4 revive inside
            frac = rng.uniform(0.4, 0.6) if is_inside \
                else rng.uniform(1.3, 1.5)
            c.kill_osd(victim)
            try:
                c.wait_for_down(victim, timeout=30 * load)
            except TimeoutError:
                pass                     # blip faster than detection:
            #                              still a valid revive pulse
            time.sleep(frac * win)
            c.revive_osd(victim)
            if is_inside:
                inside += 1
            c.wait_for_clean(timeout=90 * load)
            log(f"  pulse {pulse}: osd.{victim} "
                f"{'inside' if is_inside else 'outside'} "
                f"(bytes so far {_repair_bytes(c) - b0})")
        c.wait_for_clean(timeout=90 * load)
        time.sleep(1.0 * load)           # let async persists settle
        audit = _verify(c, cl, objects)
        # r19: fold the daemons' flame profiles BEFORE shutdown (the
        # clusters are storm-local, so this is the only window)
        from ceph_tpu.utils.profiler import profile_block
        pblock = profile_block(
            [d.profiler.dump() for d in c.osds.values()
             if not d._stop.is_set() and hasattr(d, "profiler")])
        return {
            "profile": pblock,
            "seed": seed, "delay_s": delay, "integrity": integrity,
            "pulses": pulses, "revives_inside": inside,
            "revives_inside_fraction": round(inside / pulses, 3),
            "repair_bytes": _repair_bytes(c) - b0,
            "elapsed_s": round(time.monotonic() - t0, 1),
            "policy_counters": _policy_counters(c),
            "verify": audit,
        }
    finally:
        c.shutdown()


def run_rack_loss(shard_bytes: int = 1 << 20, log=print) -> dict:
    """Deterministic exposure accounting for a rack loss, mapped
    through the real CRUSH hierarchy and ordered by the real policy
    key. The rule separates hosts (not racks), so a downed rack
    takes 1..m shards from different PGs — exactly the mixed-risk
    queue risk ordering exists for."""
    from ceph_tpu.crush.map import (Tunables, build_hierarchy,
                                    ec_rule)
    from ceph_tpu.osd.osdmap import OSDMap, PGPool
    from ceph_tpu.osd.repairpolicy import exposure_units, risk_key

    crush = build_hierarchy(32, osds_per_host=2, hosts_per_rack=2)
    crush.tunables = Tunables(choose_total_tries=51)
    ec_rule(crush, 1, choose_type=1)
    om = OSDMap(crush)
    om.add_pool(PGPool(1, pg_num=256, size=5, min_size=2,
                       crush_rule=1, is_erasure=True))
    rack = crush.domain_of(0)
    down = {o for o in range(32) if crush.domain_of(o) == rack}
    queue = []
    hist = {}
    for ps in range(256):
        acting = om.pg_to_up_acting_osds(1, ps)[2]
        lost = sum(1 for o in acting if o in down)
        if not lost:
            continue
        hist[lost] = hist.get(lost, 0) + 1
        at_m1 = (M - lost) <= 1
        queue.append((ps, float(lost * shard_bytes), at_m1, lost))
    pgid_order = [(ps, cost, m1) for ps, cost, m1, _l in queue]
    risk_order = [(ps, cost, m1) for ps, cost, m1, lost in
                  sorted(queue, key=lambda e: risk_key(
                      M - e[3], e[1], e[0]))]
    exp_pgid = exposure_units(pgid_order)
    exp_risk = exposure_units(risk_order)
    out = {
        "downed_rack_osds": sorted(down),
        "pgs_touched": len(queue),
        "lost_histogram": {str(k): v for k, v in sorted(hist.items())},
        "stripes_at_m1": sum(1 for e in queue if e[2]),
        "exposure_pgid": exp_pgid,
        "exposure_risk": exp_risk,
        "ratio_risk_vs_pgid": round(exp_risk / max(1.0, exp_pgid), 4),
    }
    log(f"rack loss: {len(queue)} PGs touched, "
        f"{out['stripes_at_m1']} at m-1; exposure risk/pgid = "
        f"{out['ratio_risk_vs_pgid']}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=1213)
    ap.add_argument("--pulses", type=int, default=4)
    ap.add_argument("--delay", type=float, default=None,
                    help="deferred-mode osd_repair_delay seconds "
                         "(default 6.0 x load factor)")
    ap.add_argument("--out", default=None, metavar="JSON")
    ap.add_argument("--json-only", action="store_true")
    args = ap.parse_args(argv)
    log = (lambda *a: None) if args.json_only else print

    from ceph_tpu.chaos.thrasher import load_factor
    load = load_factor()
    delay = args.delay if args.delay is not None else 6.0 * load

    import jax
    t0 = time.monotonic()
    log(f"storm (load {load:.1f}, delay {delay:.1f}s): eager pass")
    eager = run_storm(args.seed, 0.0, "auto", args.pulses, load, log)
    log("storm: deferred pass (host integrity)")
    def_host = run_storm(args.seed, delay, "host", args.pulses, load,
                         log)
    log("storm: deferred pass (device integrity)")
    def_dev = run_storm(args.seed, delay, "device", args.pulses,
                        load, log)
    rack = run_rack_loss(log=log)

    # r19: one profile block per artifact (the deferred-host arm —
    # the headline cell); the per-arm copies would triple the size
    profile = def_host.pop("profile", None)
    eager.pop("profile", None)
    def_dev.pop("profile", None)

    ratio = round(max(def_host["repair_bytes"],
                      def_dev["repair_bytes"])
                  / max(1, eager["repair_bytes"]), 4)
    violations = (eager["verify"]["violations"]
                  + def_host["verify"]["violations"]
                  + def_dev["verify"]["violations"])
    result = {
        "schema": SCHEMA,
        "backend": jax.default_backend(),
        "config": {
            "profile": PROFILE, "n_osds": N_OSDS, "pg_num": PG_NUM,
            "cephx": True, "secure": True, "seed": args.seed,
            "pulses": args.pulses, "delay_s": round(delay, 2),
            "load_factor": round(load, 2),
        },
        "cells": {
            "transient_storm": {
                "eager": eager,
                "deferred_host": def_host,
                "deferred_device": def_dev,
                "ratio_deferred_vs_eager": ratio,
            },
            "rack_loss": rack,
        },
        "acceptance": {
            "deferred_vs_eager_repair_bytes": ratio,
            "revives_inside_fraction":
                def_host["revives_inside_fraction"],
            "risk_vs_pgid_exposure": rack["ratio_risk_vs_pgid"],
            "invariant_violations": violations,
            "bit_exact_both_integrity_modes":
                def_host["verify"]["violations"] == 0
                and def_dev["verify"]["violations"] == 0
                and def_host["verify"]["oracle_checked"] > 0
                and def_dev["verify"]["oracle_checked"] > 0,
        },
        "elapsed_s": round(time.monotonic() - t0, 1),
    }
    if profile is not None:
        result["profile"] = profile
    text = json.dumps(result, indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        if not args.json_only:
            print(f"repair_bench: wrote {args.out}")
    if args.json_only or not args.out:
        print(text)


if __name__ == "__main__":
    main()
