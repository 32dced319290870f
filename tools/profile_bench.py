"""profile_bench — the Round-19 continuous-profiling acceptance
driver (writes BENCH_r19.json).

Three cells on ONE live cephx+secure cluster:

* flame_assembly — the monitor assembles a cluster CPU flame from
  every daemon's sampling ring over the MgrReport pipe; `ceph_cli
  flame --speedscope` (a real subprocess against the admin sockets)
  exports a valid speedscope document.
* burn_attribution — `osd_inject_cpu_burn` busy-spins inside the
  osd.op span; tools/profile_diff.py must attribute the before/after
  window delta to the injected loop: a positive stack mover for the
  loop's own frame, tagged with the op-path category ("other" —
  osd.op carries no narrower tag, the same bucket the trace
  critical-path charges it to). Stack-grain check because the mover
  DIRECTION is deterministic on any load, while category shares
  swing with messenger polling-loop noise in short windows.
* overhead_guard — >= 6 interleaved ON/OFF pairs of a fixed-op
  client round, toggled LIVE via `daemon_profile_hz` (default hz vs
  0 — same binary, same cluster, same objects; the r18 method).
  Decision statistic: median of pairwise OFF/ON throughput ratios
  (the ON slowdown); the acceptance bound is ~1.05x.

  python tools/profile_bench.py --out BENCH_r19.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "profile_r19/1"
N_OSDS = 3
PG_NUM = 2
PROFILE = "plugin=tpu_rs k=2 m=1"


def _delta_block(osds, before: dict, wall_s: float) -> dict:
    """Per-window profile block: the daemons' cumulative dumps minus
    the window-start snapshots (so adjacent windows don't bleed)."""
    from ceph_tpu.utils.perf_counters import dump_delta
    from ceph_tpu.utils.profiler import profile_block
    dumps = []
    for d in osds:
        if d._stop.is_set() or not hasattr(d, "profiler"):
            continue
        cur = d.profiler.dump()
        prev = before.get(d.name) or {"stacks": {}, "samples": 0,
                                      "sampler_busy_s": 0.0}
        dumps.append({
            "name": d.name, "hz": cur["hz"],
            "samples": cur["samples"] - prev["samples"],
            "stacks": dump_delta(prev["stacks"], cur["stacks"]),
            "sampler_busy_s": round(cur["sampler_busy_s"]
                                    - prev["sampler_busy_s"], 6),
            "uptime_s": wall_s,
        })
    return profile_block(dumps)


def _snap(osds) -> dict:
    return {d.name: d.profiler.dump() for d in osds
            if not d._stop.is_set() and hasattr(d, "profiler")}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--pairs", type=int, default=6,
                    help="interleaved ON/OFF pairs (>= 6 for the pin)")
    ap.add_argument("--round-ops", type=int, default=48,
                    help="client writes per overhead round")
    ap.add_argument("--object-size", type=int, default=8192)
    ap.add_argument("--burn", type=float, default=0.02,
                    help="osd_inject_cpu_burn seconds per op")
    ap.add_argument("--burn-ops", type=int, default=40,
                    help="ops per burn-attribution window")
    ap.add_argument("--out", default=None, metavar="JSON")
    ap.add_argument("--json-only", action="store_true")
    args = ap.parse_args(argv)
    log = (lambda *a: None) if args.json_only else print

    from ceph_tpu.chaos.thrasher import load_factor
    from ceph_tpu.osd.standalone import StandaloneCluster
    from ceph_tpu.utils.profiler import PROFILE_CATEGORIES
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from profile_diff import diff_blocks

    import jax
    load = load_factor()
    t0 = time.monotonic()
    secret = os.urandom(32)
    c = StandaloneCluster(n_osds=N_OSDS, pg_num=PG_NUM,
                          profile=PROFILE, cephx=True, secret=secret)
    try:
        c.wait_for_clean(timeout=40 * load)
        cl = c.client()
        cl.config_set("mgr_history_interval", 0.5)
        cl.config_set("mgr_report_interval", 0.5)
        default_hz = float(next(iter(c.osds.values()))
                           .config.get("daemon_profile_hz"))
        daemons = list(c.osds.values()) + list(c.mons)
        payload = os.urandom(args.object_size)

        def round_ops(n: int, prefix: str) -> float:
            t = time.perf_counter()
            for i in range(n):
                cl.write({f"{prefix}-{i}": payload})
            return time.perf_counter() - t

        # -- cell 1: flame assembly over the MgrReport pipe -----------
        log(f"flame assembly (load {load:.1f}, hz {default_hz})")
        mon = next(m for m in c.mons if not m._stop.is_set())
        deadline = time.monotonic() + 40 * load
        while time.monotonic() < deadline:
            round_ops(8, "warm")
            st = mon.profiles.stats()
            if len(st) >= 3 and \
                    sum(d["samples"] for d in st.values()) > 50:
                break
            time.sleep(0.3)
        cpu = cl.mon_command("profile cpu")
        ss_path = os.path.join(tempfile.mkdtemp(prefix="r19-"),
                               "flame.json")
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "ceph_cli.py"),
             "--asok-dir", c.admin_dir, "flame",
             "--speedscope", ss_path],
            capture_output=True, text=True, timeout=120 * load)
        ss_valid = False
        if r.returncode == 0:
            with open(ss_path) as f:
                doc = json.load(f)
            prof0 = doc["profiles"][0]
            ss_valid = (doc["$schema"] == "https://www.speedscope.app"
                        "/file-format-schema.json"
                        and prof0["endValue"]
                        == sum(prof0["weights"]) > 0)
        flame_cell = {
            "daemons": cpu.get("daemons") or [],
            "daemons_reporting": len(cpu.get("daemons") or []),
            "samples": cpu.get("samples", 0),
            "categories": cpu.get("categories"),
            "speedscope_valid": ss_valid,
            "speedscope_stacks": len(prof0["samples"])
            if ss_valid else 0,
        }
        log(f"  {flame_cell['daemons_reporting']} daemons, "
            f"{flame_cell['samples']} samples, speedscope "
            f"{'ok' if ss_valid else 'INVALID'}")

        # -- cell 2: burn attribution via profile_diff ----------------
        log(f"burn attribution ({args.burn}s busy-spin per op)")
        snap = _snap(daemons)
        tw = time.perf_counter()
        round_ops(args.burn_ops, "base")
        before_block = _delta_block(daemons, snap,
                                    time.perf_counter() - tw)
        cl.config_set("osd_inject_cpu_burn", args.burn)
        time.sleep(0.3)          # let every OSD observe the option
        snap = _snap(daemons)
        tw = time.perf_counter()
        round_ops(args.burn_ops, "burn")
        after_block = _delta_block(daemons, snap,
                                   time.perf_counter() - tw)
        cl.config_set("osd_inject_cpu_burn", 0)
        diff = diff_blocks(before_block, after_block, threshold=0.05)
        # osd.op carries no narrower span tag, so the burn's frames
        # land in "other" — the bucket the trace critical-path
        # charges osd.op self-time to. Category SHARES can't isolate
        # it (in-span waits keep "other" near-saturated on this op
        # mix, and messenger polling loops swing whole share points
        # between short windows), but the STACK mover can: the diff
        # must report a POSITIVE mover for the injected loop's own
        # frame, tagged with the expected category — attribution at
        # the grain the diff tool exists for
        burn_movers = [m for m in diff["top_movers"]
                       if "_one_client_op" in m["stack"]
                       and m["delta_share"] > 0]
        burn_mover = max(burn_movers,
                         key=lambda m: m["delta_share"], default={})
        attributed = burn_mover.get("category") == "other"
        burn_cell = {
            "burn_s_per_op": args.burn,
            "ops_per_window": args.burn_ops,
            "expected_category": "other",
            "expected_frame": "standalone:_one_client_op",
            "before_share": diff["categories"]["other"]["before_share"],
            "after_share": diff["categories"]["other"]["after_share"],
            "burn_mover": burn_mover,
            "top_movers": diff["top_movers"],
            "regressed": diff["regressed"],
            "verdict": diff["verdict"],
            "attributed": attributed,
        }
        log(f"  burn mover [{burn_mover.get('category', '?')}] "
            f"...{burn_mover.get('stack', '?')[-52:]} "
            f"{burn_mover.get('delta_share', 0):+.1%} -> "
            f"{'attributed' if attributed else 'NOT ATTRIBUTED'}")

        # -- cell 3: interleaved ON/OFF overhead guard ----------------
        log(f"overhead guard ({args.pairs} pairs x "
            f"{args.round_ops} ops)")
        pairs = []
        for p in range(args.pairs):
            cl.config_set("daemon_profile_hz", default_hz)
            time.sleep(0.4)      # sampler loops observe the toggle
            on = args.round_ops / round_ops(args.round_ops,
                                            f"on{p}")
            cl.config_set("daemon_profile_hz", 0)
            time.sleep(0.4)
            off = args.round_ops / round_ops(args.round_ops,
                                             f"off{p}")
            pairs.append({"on": round(on, 2), "off": round(off, 2)})
            log(f"  pair {p}: on {on:.1f} ops/s, off {off:.1f} ops/s")
        cl.config_set("daemon_profile_hz", default_hz)
        slowdowns = [p["off"] / p["on"] for p in pairs]
        med = statistics.median(slowdowns)
        guard_cell = {
            "metric": "client_write_ops_per_s",
            "hz": default_hz,
            "pairs": pairs,
            "on_median": round(statistics.median(
                p["on"] for p in pairs), 2),
            "off_median": round(statistics.median(
                p["off"] for p in pairs), 2),
            "median_pairwise_off_over_on": round(med, 4),
        }
        log(f"  median pairwise OFF/ON (ON slowdown): {med:.3f}")
    finally:
        c.shutdown()

    result = {
        "schema": SCHEMA,
        "backend": jax.default_backend(),
        "config": {
            "profile": PROFILE, "n_osds": N_OSDS, "pg_num": PG_NUM,
            "cephx": True, "secure": True,
            "hz": guard_cell["hz"], "pairs": args.pairs,
            "round_ops": args.round_ops,
            "object_size": args.object_size,
            "load_factor": round(load, 2),
            "categories": list(PROFILE_CATEGORIES),
        },
        "cells": {
            "flame_assembly": flame_cell,
            "burn_attribution": burn_cell,
            "overhead_guard": guard_cell,
        },
        "acceptance": {
            "flame_daemons_reporting":
                flame_cell["daemons_reporting"],
            "speedscope_valid": flame_cell["speedscope_valid"],
            "burn_attributed_to_expected_category":
                burn_cell["attributed"],
            "overhead_median_pairwise_slowdown":
                guard_cell["median_pairwise_off_over_on"],
            "bound": "slowdown median of >= 6 interleaved pairs "
                     "<= ~1.05 at the default hz",
        },
        "elapsed_s": round(time.monotonic() - t0, 1),
    }
    text = json.dumps(result, indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        if not args.json_only:
            print(f"profile_bench: wrote {args.out}")
    if args.json_only or not args.out:
        print(text)


if __name__ == "__main__":
    main()
