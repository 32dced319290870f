"""capacity bench — the r21 capacity-exhaustion acceptance run.

Drives a LIVE cephx + secure-frames StandaloneCluster through the
full-ratio ladder and commits the observable contract as JSON
(BENCH_r21.json, pinned by tests/test_bench_schema.py):

  * full_window — the cluster is driven to FULL mid-write-window.
    In-flight writes PARK (RADOS full-wait: zero surfaced errors,
    backoff disclosed in full_backoff_time), reads keep serving
    bit-exact, deletes pass (the implicit FULL_TRY), and after the
    window heals every parked write drains exactly-once, byte-exact.
  * backfillfull_recovery — with every target at the backfillfull
    rung, a daemon loss parks its rebuild (counted per daemon) while
    degraded reads keep serving; clearing the rung resumes recovery
    to clean, bit-exact.
  * failsafe_window — REAL capacity shrink to the 0.97 local
    hard-stop: the OSD bounces writes (writes_rejected_full), the
    client parks without surfacing, and restoring capacity drains —
    even when the window is too short for the ladder to commit.
  * enospc_matrix — one-shot ENOSPC at EVERY TinStore txn phase
    (stage apply, WAL append, flush/compaction segment + manifest),
    then SIGKILL: acked txns wholly present, the failed txn wholly
    absent, fsck clean, and the store accepts again once space
    returns.

  python tools/capacity_bench.py --json --out BENCH_r21.json
"""

from __future__ import annotations

import argparse
import errno as _errno
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ENOSPC_PHASES = ("txn.apply", "wal.append", "flush.segment-written",
                 "flush.manifest-swapped", "compact.segments-written",
                 "compact.manifest-swapped")


def _corpus(rng, n, size, prefix):
    return {f"{prefix}-{i:03d}":
            rng.integers(0, 256, size, __import__("numpy").uint8)
            .tobytes() for i in range(n)}


def _claim_ratio(c, ratio, total=10 << 20):
    """Spoof every store's statfs CLAIM at a fixed ratio (stores stay
    unbounded) — the deterministic way to fly a ladder rung without
    racing real metadata growth; the failsafe + ENOSPC cells below
    exercise REAL capacity."""
    for d in c.osds.values():
        d.store.statfs = (lambda t=total, r=ratio: {
            "total": t, "used": int(t * r),
            "avail": max(0, int(t * (1 - r)))})


def _unclaim(c):
    for d in c.osds.values():
        try:
            del d.store.statfs
        except AttributeError:
            pass


def _poll(pred, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise SystemExit(f"capacity_bench: timeout waiting for {what}")


class _Writer:
    def __init__(self, cl, objs):
        self.cl, self.objs = cl, objs
        self.errors: list[BaseException] = []
        self.t = threading.Thread(target=self._run, daemon=True)
        self.t.start()

    def _run(self):
        try:
            self.cl.write(self.objs)
        except BaseException as e:   # noqa: BLE001 — surfaced = fail
            self.errors.append(e)


def cell_full_window(secret, seed):
    import numpy as np

    from ceph_tpu.osd.standalone import StandaloneCluster
    rng = np.random.default_rng(seed)
    c = StandaloneCluster(n_osds=4, pg_num=4, op_timeout=3.0,
                          cephx=True, secret=secret)
    try:
        c.wait_for_clean(timeout=30)
        cl = c.client()
        base = _corpus(rng, 24, 700, "full-base")
        cl.write(base)
        _claim_ratio(c, 0.96)            # over full, under failsafe
        _poll(lambda: cl.mon_command("df")["cluster_full"], 30,
              "the FULL flag")
        cl2 = c.client()
        parked = _corpus(rng, 6, 700, "full-parked")
        w = _Writer(cl2, parked)
        time.sleep(1.0)
        window_writer_alive = w.t.is_alive() and not w.errors
        reads_served = 0
        for name, want in base.items():
            if cl.read(name) == want:
                reads_served += 1
        victim = sorted(base)[0]
        cl.remove([victim])              # implicit FULL_TRY
        delete_passed = True
        try:
            cl.read(victim)
            delete_passed = False
        except KeyError:
            pass
        still_parked = w.t.is_alive() and not w.errors
        _unclaim(c)
        _poll(lambda: not cl.mon_command("df")["cluster_full"], 30,
              "the FULL flag clearing")
        w.t.join(45)
        drained = not w.t.is_alive() and not w.errors
        bit_exact = drained and all(
            cl.read(n) == v for n, v in parked.items())
        fb = cl2.perf.dump().get("full_backoff_time") or {}
        return {
            "n_osds": 4, "cephx": True, "secure": True,
            "base_objects": len(base),
            "parked_writes": len(parked),
            "writer_parked_during_window":
                bool(window_writer_alive and still_parked),
            "reads_served_under_full": reads_served,
            "delete_passed_under_full": bool(delete_passed),
            "parked_drained": len(parked) if drained else 0,
            "drained_bit_exact": bool(bit_exact),
            "client_op_errors": len(w.errors),
            "full_backoff": {
                "count": int(fb.get("avgcount", 0)),
                "total_s": round(float(fb.get("sum", 0.0)), 3)},
        }
    finally:
        c.shutdown()


def cell_backfillfull_recovery(secret, seed):
    import numpy as np

    from ceph_tpu.osd.standalone import StandaloneCluster
    rng = np.random.default_rng(seed)
    c = StandaloneCluster(n_osds=7, pg_num=4, op_timeout=3.0,
                          cephx=True, secret=secret,
                          profile="plugin=tpu_rs k=2 m=3")
    try:
        c.wait_for_clean(timeout=30)
        cl = c.client()
        base = _corpus(rng, 20, 700, "bff-base")
        cl.write(base)
        _claim_ratio(c, 0.92)            # backfillfull, not full
        _poll(lambda: any(ch["code"] == "OSD_BACKFILLFULL"
                          for ch in cl.health()["checks"]), 30,
              "the backfillfull rung")
        victim = cl.osdmap.pg_to_up_acting_osds(1, 0)[2][0]
        c.kill_osd(victim)
        c.wait_for_down(victim)

        def live():
            return [d for d in c.osds.values()
                    if not d._stop.is_set()]

        def parked_total():
            return sum(d.repair_policy.counters[
                "repair_backfillfull_parked"] for d in live())
        _poll(lambda: parked_total() > 0, 30,
              "a rebuild parking on a backfillfull target")
        degraded_served = 0
        for name in sorted(base)[:6]:
            if cl.read(name) == base[name]:
                degraded_served += 1
        parked = parked_total()
        _unclaim(c)
        _poll(lambda: not any(ch["code"] == "OSD_BACKFILLFULL"
                              for ch in cl.health()["checks"]), 30,
              "the rung clearing")
        c.wait_for_clean(timeout=60)
        bit_exact = all(cl.read(n) == v for n, v in base.items())
        return {
            "n_osds": 7, "profile": "k=2 m=3",
            "victim": victim,
            "recovery_parked_backfillfull": int(parked),
            "degraded_reads_served": degraded_served,
            "recovered_clean_after_clear": True,
            "recovered_bit_exact": bool(bit_exact),
        }
    finally:
        c.shutdown()


def cell_failsafe_window(seed):
    import numpy as np

    from ceph_tpu.osd.standalone import StandaloneCluster
    rng = np.random.default_rng(seed)
    c = StandaloneCluster(n_osds=4, pg_num=4, op_timeout=3.0)
    try:
        c.wait_for_clean(timeout=30)
        cl = c.client()
        # park the map-level full rung out of reach: the REAL shrunk
        # stores below sit between failsafe (0.97) and full (0.999),
        # so the local hard-stop is the only gate
        cl.config_set("mon_osd_full_ratio", "0.999")
        base = _corpus(rng, 20, 700, "fs-base")
        cl.write(base)
        for d in c.osds.values():
            used = d.store.statfs()["used"]
            d.store.set_capacity(max(1, int(used / 0.98)))
        w = _Writer(cl, _corpus(rng, 2, 700, "fs-parked"))

        def rejected():
            return sum(d.perf.get("writes_rejected_full")
                       for d in c.osds.values())
        _poll(lambda: rejected() > 0, 30, "a failsafe rejection")
        time.sleep(0.5)
        parked = w.t.is_alive() and not w.errors
        rej = rejected()
        for d in c.osds.values():
            d.store.set_capacity(0)
        w.t.join(45)
        drained = not w.t.is_alive() and not w.errors
        bit_exact = drained and all(
            cl.read(n) == v for n, v in w.objs.items())
        return {
            "writes_rejected_full": int(rej),
            "writer_parked_during_window": bool(parked),
            "parked_drained": len(w.objs) if drained else 0,
            "drained_bit_exact": bool(bit_exact),
            "client_op_errors": len(w.errors),
        }
    finally:
        c.shutdown()


def cell_enospc_matrix(tmp_root):
    from ceph_tpu.osd.memstore import Transaction
    from ceph_tpu.osd.tinstore import TinStore
    rows = {}
    for phase in ENOSPC_PHASES:
        path = os.path.join(tmp_root,
                            f"enospc-{phase.replace('.', '-')}")
        # tiny WAL budget + fanout so flush and compaction phases
        # are reached within a few dozen small txns
        st = TinStore(path, wal_max_bytes=2048, kv_fanout=2)
        st.queue_transaction(
            Transaction().create_collection("c")
            .write("c", "base", 0, b"B" * 512))
        fired = {"n": 0}

        def fault(point, ph=phase):
            if point == ph and fired["n"] == 0:
                fired["n"] = 1
                raise OSError(_errno.ENOSPC, f"injected at {ph}")
        st.set_fault(fault)
        acked = {}
        for i in range(200):
            if fired["n"]:
                break
            name, data = f"o{i}", bytes([i % 251]) * 300
            try:
                st.queue_transaction(
                    Transaction().write("c", name, 0, data))
                acked[name] = data
            except OSError:
                pass                      # aborted txn: wholly absent
        st.crash()                        # SIGKILL mid-abort
        rep = TinStore.fsck(path)
        clean = not rep["errors"] and not rep.get("bad_objects")
        st.remount()
        ok = bytes(st.read("c", "base")) == b"B" * 512
        for name, data in acked.items():
            ok = ok and bytes(st.read("c", name)) == data
        st.set_fault(None)
        st.queue_transaction(
            Transaction().write("c", "post", 0, b"P" * 64))
        ok = ok and bytes(st.read("c", "post")) == b"P" * 64
        st.umount()
        rows[phase] = {"fired": fired["n"], "acked": len(acked),
                       "fsck_clean": bool(clean),
                       "acked_bit_exact_and_accepts_after":
                       bool(ok)}
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--tmp", default="/tmp/capacity_bench",
                    help="scratch dir for the TinStore ENOSPC matrix")
    ap.add_argument("--out", default=None)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from ceph_tpu.utils.jax_cache import enable_persistent_compile_cache
    enable_persistent_compile_cache()

    import shutil
    shutil.rmtree(args.tmp, ignore_errors=True)
    os.makedirs(args.tmp, exist_ok=True)
    secret = b"capacity bench secret key 32b!!!"

    full = cell_full_window(secret, args.seed)
    bff = cell_backfillfull_recovery(secret, args.seed + 1)
    fs = cell_failsafe_window(args.seed + 2)
    matrix = cell_enospc_matrix(args.tmp)

    acceptance = {
        "client_op_errors": full["client_op_errors"]
        + fs["client_op_errors"],
        "reads_served_under_full": full["reads_served_under_full"],
        "delete_passed_under_full": full["delete_passed_under_full"],
        "parked_drained_fraction": 1.0 if (
            full["parked_drained"] == full["parked_writes"]
            and fs["parked_drained"] > 0) else 0.0,
        "drained_bit_exact": full["drained_bit_exact"]
        and fs["drained_bit_exact"],
        "recovery_parked_backfillfull":
            bff["recovery_parked_backfillfull"],
        "degraded_reads_served_under_backfillfull":
            bff["degraded_reads_served"],
        "failsafe_writes_rejected": fs["writes_rejected_full"],
        "enospc_phases_covered": sum(
            1 for r in matrix.values() if r["fired"]),
        "enospc_all_fsck_clean": all(
            r["fsck_clean"] and r["acked_bit_exact_and_accepts_after"]
            for r in matrix.values()),
    }
    out = {
        "schema": "capacity_r21/1",
        "config": {"seed": args.seed, "cephx": True, "secure": True,
                   "full_ratios": {"nearfull": 0.85,
                                   "backfillfull": 0.90,
                                   "full": 0.95, "failsafe": 0.97}},
        "cells": {"full_window": full,
                  "backfillfull_recovery": bff,
                  "failsafe_window": fs,
                  "enospc_matrix": matrix},
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
