"""netobs bench — the r22 network-observability acceptance run.

Drives LIVE cephx + secure-frames StandaloneClusters through the
r22 contract and commits the observable evidence as JSON
(BENCH_r22.json, pinned by tests/test_bench_schema.py):

  * link_degrade — a one-way delay injected on osd.a's transmits
    toward osd.b (heartbeat pings included; pongs cross undelayed)
    must flip OSD_SLOW_PING_TIME naming EXACTLY that directed link
    within two grace windows (plus report cadence), and the check
    must clear after the heal. time-to-flip and time-to-clear are
    recorded against their budgets.
  * helper_avoidance — with the same degrade standing, the r14
    helper-cost ranking must reprice the degraded peer worst
    (counter-pinned: net_helper_penalties moves), and the mon's
    link_cost(a, b) feed must separate the degraded edge from a
    healthy one by a wide margin.
  * overhead_guard — the r15/r18 interleaved-pair protocol: >= 6
    same-binary ON/OFF pairs of a fixed wire write workload, OFF =
    `config set osd_network_observability false` (stops the RTT
    folds and the report side-field — the whole toggleable plane).
    Decision statistic: median of pairwise ON/OFF throughput
    ratios, must sit in [0.95, 1.10] (the r15 noise envelope).
    Pair order alternates ON-first/OFF-first so warm-up drift
    cancels across pairs, not just inside them.

  python tools/netobs_bench.py --json --out BENCH_r22.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _poll(pred, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.1)
    raise SystemExit(f"netobs_bench: timeout waiting for {what}")


def _slow_ping_check(cl):
    try:
        h = cl.health(detail=True)
    except Exception:   # noqa: BLE001 — mon hunt mid-poll
        return None
    return next((ck for ck in h["checks"]
                 if ck["code"] == "OSD_SLOW_PING_TIME"), None)


def _boot(secret, n_osds=4, pg_num=4):
    from ceph_tpu.osd.standalone import StandaloneCluster
    c = StandaloneCluster(n_osds=n_osds, pg_num=pg_num,
                          hb_interval=0.25, hb_grace=2.0,
                          op_timeout=5.0, cephx=True, secret=secret,
                          profile="plugin=tpu_rs k=2 m=1")
    c.wait_for_clean(timeout=40)
    cl = c.client()
    cl.config_set("mgr_report_interval", 0.5)
    return c, cl


def cell_link_degrade(secret, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    c, cl = _boot(secret)
    try:
        cl.config_set("mon_warn_on_slow_ping_time", 100.0)
        cl.write({f"ld-{i:02d}": rng.integers(0, 256, 600, np.uint8)
                  .tobytes() for i in range(8)})
        # matrix warm (hb links carry >= MIN_SAMPLES) before the clock
        # starts: time-to-flip measures detection, not boot
        _poll(lambda: any(r["channel"] == "hb" and r["count"] >= 3
                          for r in cl.mon_command(
                              "dump_osd_network")["links"]),
              20, "a warm hb link matrix")
        grace = float(c.osds[0].config["osd_heartbeat_grace"])
        report_s = float(c.osds[0].config["mgr_report_interval"])
        flip_budget = 2.0 * grace + 2.0 * report_s + 2.0
        a, b, delay_ms, jitter_ms = 0, 2, 300.0, 25.0
        want = f"osd.{a} -> osd.{b} (hb)"
        t0 = time.monotonic()
        c.link_degrade(a, b, delay_ms, jitter_ms, seed=seed)
        fired = _poll(lambda: _slow_ping_check(cl), flip_budget + 10,
                      "OSD_SLOW_PING_TIME")
        flip_s = time.monotonic() - t0
        named_exact = (any(want in ln for ln in fired["detail"])
                       and not [ln for ln in fired["detail"]
                                if want not in ln])
        t1 = time.monotonic()
        c.heal_link_degrades()
        clear_budget = flip_budget + 4.0
        _poll(lambda: _slow_ping_check(cl) is None, clear_budget + 10,
              "OSD_SLOW_PING_TIME clearing")
        clear_s = time.monotonic() - t1
        suspects = int(c.osds[a].perf.dump()["slow_link_suspects"])
        return {
            "n_osds": 4, "cephx": True, "secure": True,
            "degraded_link": want,
            "delay_ms": delay_ms, "jitter_ms": jitter_ms,
            "threshold_ms": 100.0,
            "grace_s": grace, "report_interval_s": report_s,
            "flip_s": round(flip_s, 3),
            "flip_budget_s": round(flip_budget, 3),
            "flipped_within_budget": bool(flip_s <= flip_budget),
            "named_exact_link": bool(named_exact),
            "detail": fired["detail"],
            "clear_s": round(clear_s, 3),
            "clear_budget_s": round(clear_budget, 3),
            "cleared_within_budget": bool(clear_s <= clear_budget),
            "slow_link_suspects": suspects,
        }
    finally:
        c.shutdown()


def cell_helper_avoidance(secret, seed):
    from types import SimpleNamespace

    import numpy as np
    rng = np.random.default_rng(seed)
    c, cl = _boot(secret)
    try:
        cl.config_set("mon_warn_on_slow_ping_time", 100.0)
        cl.write({f"ha-{i:02d}": rng.integers(0, 256, 600, np.uint8)
                  .tobytes() for i in range(8)})
        a, b, healthy = 0, 3, 1
        d = c.osds[a]
        pen0 = d.perf.get("net_helper_penalties")
        live = sorted(c.osds)
        costs0 = d._helper_costs(SimpleNamespace(acting=live))
        c.link_degrade(a, b, 300.0, 0.0, seed=seed)
        # repriced when the degraded peer is the single worst-cost
        # non-self helper slot AND the declared penalty counter moved

        def repriced():
            costs = d._helper_costs(SimpleNamespace(acting=live))
            others = {o: v for o, v in costs.items() if o != a}
            worst = max(others, key=others.get)
            return (worst == b
                    and d.perf.get("net_helper_penalties") > pen0
                    and costs)
        costs1 = _poll(repriced, 30, "the helper ranking to reprice")
        pen1 = d.perf.get("net_helper_penalties")
        # the mon-side feed: the degraded directed edge vs a healthy
        # one (µs, minimum_to_decode_with_cost units)
        feed = _poll(lambda: (
            c.mons[0].netobs.link_cost(a, b) >
            10 * max(1, c.mons[0].netobs.link_cost(a, healthy))
            and {"degraded_us": c.mons[0].netobs.link_cost(a, b),
                 "healthy_us": c.mons[0].netobs.link_cost(a, healthy)}),
            30, "the mon link_cost feed to separate the edges")
        return {
            "n_osds": 4, "cephx": True, "secure": True,
            "degraded_peer": b, "healthy_peer": healthy,
            "costs_before": {f"osd.{o}": int(v)
                             for o, v in costs0.items()},
            "costs_after": {f"osd.{o}": int(v)
                            for o, v in costs1.items()},
            "degraded_priced_worst": True,
            "net_helper_penalties_before": int(pen0),
            "net_helper_penalties_after": int(pen1),
            "penalties_moved": bool(pen1 > pen0),
            "mon_link_cost_us": feed,
        }
    finally:
        c.shutdown()


def cell_overhead_guard(secret, seed, pairs=6, objects=64,
                        size=65536, reps=5):
    import numpy as np
    rng = np.random.default_rng(seed)
    c, cl = _boot(secret)
    try:
        payloads = [rng.integers(0, 256, size, np.uint8).tobytes()
                    for _ in range(objects)]

        def arm(tag):
            t0 = time.monotonic()
            for _ in range(reps):
                cl.write({f"og-{i:03d}": payloads[i]
                          for i in range(objects)})
            dt = time.monotonic() - t0
            return round(reps * objects * size / dt / (1 << 20), 2)

        def set_on(on):
            cl.config_set("osd_network_observability",
                          "true" if on else "false")
            time.sleep(0.1)

        cl.write({f"og-{i:03d}": payloads[i]
                  for i in range(objects)})   # warm the write path
        rows = []
        for p in range(pairs):
            order = ("on", "off") if p % 2 == 0 else ("off", "on")
            got = {}
            for which in order:
                set_on(which == "on")
                got[which] = arm(which)
            rows.append({"on": got["on"], "off": got["off"],
                         "order": "/".join(order)})
        set_on(True)
        ratios = sorted(r["on"] / r["off"] for r in rows)
        med = round(statistics.median(ratios), 3)
        return {
            "metric": "mb_per_s",
            "knob": "osd_network_observability (config set, live)",
            "workload": f"wire write {objects} x {size}B x {reps} "
                        f"passes per arm, cephx+secure",
            "pairs": rows,
            "on_median": round(statistics.median(
                r["on"] for r in rows), 2),
            "off_median": round(statistics.median(
                r["off"] for r in rows), 2),
            "median_pairwise_on_over_off": med,
        }
    finally:
        c.shutdown()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--pairs", type=int, default=6)
    ap.add_argument("--out", default=None)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from ceph_tpu.utils.jax_cache import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    secret = b"netobs bench secret key 32bytes!"

    ld = cell_link_degrade(secret, args.seed)
    ha = cell_helper_avoidance(secret, args.seed + 1)
    og = cell_overhead_guard(secret, args.seed + 2, pairs=args.pairs)

    acceptance = {
        "flip_within_two_grace_windows": ld["flipped_within_budget"],
        "named_exact_link": ld["named_exact_link"],
        "cleared_after_heal": ld["cleared_within_budget"],
        "helper_repriced_counter_pinned": ha["penalties_moved"]
        and ha["degraded_priced_worst"],
        "overhead_median_pairwise": og["median_pairwise_on_over_off"],
        "bound": "overhead median within [0.95, 1.10] of parity "
                 "(the r15 noise envelope)",
    }
    out = {
        "schema": "netobs_r22/1",
        "date": "2026-08-07",
        "protocol": "r15 interleaved-pair method, same-binary knob: "
                    "OFF = config set osd_network_observability "
                    "false; >=6 pairs, alternating arm order; "
                    "decision statistic = median of pairwise ON/OFF "
                    "ratios (load cancels inside a pair)",
        "config": {"seed": args.seed, "cephx": True, "secure": True,
                   "hb_interval_s": 0.25, "hb_grace_s": 2.0,
                   "mgr_report_interval_s": 0.5,
                   "profile": "plugin=tpu_rs k=2 m=1"},
        "cells": {"link_degrade": ld,
                  "helper_avoidance": ha,
                  "overhead_guard": og},
        "acceptance": acceptance,
    }
    text = json.dumps(out, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(out, indent=1, sort_keys=True) + "\n")
    if args.json:
        print(text)
    else:
        print(f"  acceptance: {json.dumps(acceptance, indent=1)}")


if __name__ == "__main__":
    main()
