"""An OSD that is down and not yet out (ref: OSDMonitor::tick,
`mon_osd_down_out_interval`): the monitor marks a failed OSD down at
once and out only an interval later, so for that long the pool stays
degraded, nothing is re-slotted, no recovery starts, and every read of
a PG that lost a data slot is rebuilt from k survivors on the device.

Wire tier at a small size: k=4 m=2, 64 KiB objects, 7 OSDs, seeded.
The reads are held to what was written and to the benchmark's plain
reference (`bench/reference/rs_decode.py`, Gauss-Jordan in numpy) on
the rows the survivors store."""

import time

import numpy as np
import pytest

from bench.reference import gf256
from bench.reference.rs_decode import rs_decode
from ceph_tpu.chaos import load_factor
from ceph_tpu.osd.ecbackend import shard_cid
from ceph_tpu.osd.standalone import StandaloneCluster

_LF = load_factor()
K, M = 4, 2
UNIT = 256
SIZE = 64 << 10
PROFILE = f"plugin=jerasure technique=reed_sol_van k={K} m={M}"
OP_TIMEOUT = 3.0


def make(**kw):
    """7 OSDs; a heartbeat grace no loaded host trips by itself unless
    the test asks for the failure path."""
    kw.setdefault("hb_interval", 0.5)
    kw.setdefault("hb_grace", 30.0)
    c = StandaloneCluster(n_osds=7, pg_num=4, profile=PROFILE,
                          op_timeout=OP_TIMEOUT, chunk_size=UNIT, **kw)
    c.wait_for_clean(timeout=30 * _LF)
    return c


def corpus(seed, n=16):
    rng = np.random.default_rng(seed)
    return {f"dno-{seed}-{i}": rng.integers(0, 256, SIZE, np.uint8).tobytes()
            for i in range(n)}


def mon_map(c):
    return max((m.osdmap for m in c.mons if m.osdmap is not None),
               key=lambda m: m.epoch)


def live(c):
    return [d for d in c.osds.values() if not d._stop.is_set()]


def counter(c, key):
    return sum(int(d.ec_perf.get(key)) for d in live(c))


def non_primary(c, cl):
    """The OSD that is no PG's primary and holds a data slot in the
    most PGs (the benchmark's victim rule)."""
    acting = [cl.osdmap.pg_to_up_acting_osds(1, ps)[2]
              for ps in range(c.pg_num)]
    primaries = {a[0] for a in acting}
    return max((o for o in c.osd_ids() if o not in primaries),
               key=lambda o: (sum(o in a[:K] for a in acting), -o))


def wait_maps_show_down(c, osd):
    c._wait(lambda: all(not d.osdmap.osd_up[osd] for d in live(c)),
            15 * _LF, f"every daemon's map shows osd.{osd} down")


@pytest.fixture(scope="module")
def degraded():
    """One pool, written whole, then one non-primary OSD killed and
    marked down by the admin `down`; the interval keeps it in."""
    c = make(down_out_interval=600.0)
    try:
        cl = c.client(hedge_delay_ms=-1)      # no hedged twin: counts exact
        objs = corpus(27)
        cl.write(objs)
        victim = non_primary(c, cl)
        acting = {ps: list(cl.osdmap.pg_to_up_acting_osds(1, ps)[2])
                  for ps in range(c.pg_num)}
        before = {k: counter(c, k) for k in ("recover_launches",
                                             "recovered_objects")}
        c.kill_osd(victim)
        t0 = time.monotonic()
        cl.osd_down(victim)
        took = time.monotonic() - t0
        wait_maps_show_down(c, victim)
        yield {"c": c, "cl": cl, "objs": objs, "victim": victim,
               "acting": acting, "before": before, "down_took": took}
    finally:
        c.shutdown()


def slot_lost(d, name):
    ps = d["cl"].osdmap.object_to_pg(1, name)[1]
    return ps, d["acting"][ps].index(d["victim"]) \
        if d["victim"] in d["acting"][ps] else None


def test_failed_osd_is_down_and_in_until_the_interval_then_out():
    interval = 4.0
    c = make(down_out_interval=interval, hb_interval=0.25,
             hb_grace=2.0 * _LF)
    try:
        cl = c.client()
        victim = non_primary(c, cl)
        c.kill_osd(victim)
        c.wait_for_down(victim, timeout=40 * _LF)
        t_down = time.monotonic()
        m = mon_map(c)
        assert not m.osd_up[victim] and m.osd_weight[victim] != 0
        c._wait(lambda: mon_map(c).osd_weight[victim] == 0,
                (interval + 20) * _LF, f"osd.{victim} marked out")
        # every monitor starts its clock when it first sees the mark
        assert time.monotonic() - t_down >= interval - 2 * c.hb_interval
        assert not mon_map(c).osd_up[victim]
        c.wait_for_clean(timeout=60 * _LF)   # out: the spare takes over
    finally:
        c.shutdown()


def test_boot_inside_the_interval_cancels_the_out():
    interval = 3.0
    c = make(down_out_interval=interval)
    try:
        cl = c.client()
        victim = non_primary(c, cl)
        c.kill_osd(victim)
        cl.osd_down(victim)
        assert mon_map(c).osd_weight[victim] != 0
        c.revive_osd(victim)
        c._wait(lambda: mon_map(c).osd_up[victim], 20 * _LF,
                f"osd.{victim} back up")
        time.sleep(interval + 4 * c.hb_interval)
        m = mon_map(c)
        assert m.osd_up[victim] and m.osd_weight[victim] != 0
        assert all(victim not in mon._down_since for mon in c.mons)
    finally:
        c.shutdown()


def test_harness_interval_is_zero_and_central_config_overrides_it():
    """Out rides the down mark unless an interval is stated; `ceph
    config set mon_osd_down_out_interval` is one way to state it."""
    c = make()
    try:
        assert c.down_out_interval == 0.0
        cl = c.client()
        acting0 = cl.osdmap.pg_to_up_acting_osds(1, 0)[2]
        first, second = [o for o in c.osd_ids() if o != acting0[0]][:2]
        c.kill_osd(first)
        cl.osd_down(first)
        m = mon_map(c)
        assert not m.osd_up[first] and m.osd_weight[first] == 0
        c.wait_for_clean(timeout=60 * _LF)
        cl.config_set("mon_osd_down_out_interval", 600)
        assert all(mon._down_out_interval() == 600.0 for mon in c.mons
                   if mon.osdmap.epoch == mon_map(c).epoch)
        c.kill_osd(second)
        cl.osd_down(second)
        time.sleep(4 * c.hb_interval)
        m = mon_map(c)
        assert not m.osd_up[second] and m.osd_weight[second] != 0
    finally:
        c.shutdown()


def test_admin_down_needs_no_heartbeat_grace(degraded):
    d, c = degraded, degraded["c"]
    assert d["down_took"] < 5.0 * _LF < c.hb_grace
    m = mon_map(c)
    assert not m.osd_up[d["victim"]] and m.osd_weight[d["victim"]] != 0
    assert set(np.flatnonzero(~np.asarray(m.osd_up, bool))) == {d["victim"]}


def test_admin_down_of_a_live_daemon_boots_it_again():
    c = make(down_out_interval=600.0, hb_interval=0.25)
    try:
        cl = c.client()
        osd = non_primary(c, cl)
        cl.osd_down(osd)
        c._wait(lambda: mon_map(c).osd_up[osd], 20 * _LF,
                f"live osd.{osd} re-asserted itself")
        assert mon_map(c).osd_weight[osd] != 0
    finally:
        c.shutdown()


def test_every_degraded_read_equals_what_was_written_and_rs_decode(degraded):
    d, c, cl = degraded, degraded["c"], degraded["cl"]
    matrix = gf256.reed_sol_van(K, M)
    kinds = set()
    for name, want in d["objs"].items():
        got = cl.read(name)
        assert got == want, name
        ps, lost = slot_lost(d, name)
        kinds.add("none" if lost is None else
                  "data" if lost < K else "parity")
        present = [s for s in range(K + M) if s != lost][:K]
        rows = np.stack([np.asarray(c.osds[d["acting"][ps][s]].store.read(
            shard_cid(f"1.{ps}", s), name), np.uint8) for s in present])
        data = rs_decode(matrix, rows, present, range(K))
        plain = data.reshape(K, -1, UNIT).transpose(1, 0, 2).reshape(-1)
        assert plain[:SIZE].tobytes() == got, name
    assert {"data", "parity"} <= kinds, kinds


def test_counters_count_the_reads_and_rows_that_were_rebuilt(degraded):
    d, c, cl = degraded, degraded["c"], degraded["cl"]
    keys = ("degraded_reads", "decode_rows_rebuilt", "decode_bytes_rebuilt",
            "decode_launches", "host_decode_launches")
    before = {k: counter(c, k) for k in keys}
    rebuilding = 0
    for name, want in d["objs"].items():
        assert cl.read(name) == want
        lost = slot_lost(d, name)[1]
        rebuilding += lost is not None and lost < K
    rose = {k: counter(c, k) - before[k] for k in keys}
    assert 0 < rebuilding < len(d["objs"])
    assert rose["degraded_reads"] == rebuilding
    assert rose["decode_rows_rebuilt"] == rebuilding
    assert rose["decode_bytes_rebuilt"] == rebuilding * (SIZE // K)
    assert rose["decode_launches"] == len(d["objs"])   # pass-throughs too
    assert rose["host_decode_launches"] == 0


def test_second_read_of_a_pg_meets_no_new_decode_pattern(degraded):
    d, c, cl = degraded, degraded["c"], degraded["cl"]
    for name in d["objs"]:
        cl.read(name)                        # every PG has met its pattern
    backends = [be for dm in live(c) for be in dm.backends.values()]
    patterns = {id(be): set(be.coder._decode_cache) for be in backends}
    plans = {id(be): dict(be._read_plans) for be in backends}
    assert any(patterns.values())
    rng = np.random.default_rng(5)
    for _ in range(3):
        for dm in live(c):                   # the costs change under it
            for osd in c.osd_ids():
                dm._peer_lat[osd] = float(rng.uniform(1e-4, 0.5))
        for name, want in d["objs"].items():
            assert cl.read(name) == want
    for be in backends:
        assert set(be.coder._decode_cache) == patterns[id(be)]
        assert be._read_plans == plans[id(be)]
        assert len(be._read_plans) <= 1


def test_a_map_down_member_costs_no_timed_out_call(degraded):
    d, c, cl = degraded, degraded["c"], degraded["cl"]
    # nobody has to have found the victim out for itself: the map says
    # down, and that is enough from its epoch on
    for dm in live(c):
        assert d["victim"] in dm._dead()
    slowest = 0.0
    for name, want in d["objs"].items():
        t0 = time.monotonic()
        assert cl.read(name) == want
        slowest = max(slowest, time.monotonic() - t0)
    assert slowest < OP_TIMEOUT / 2, slowest


def test_while_down_and_in_nothing_recovers_and_no_slot_moves(degraded):
    d, c = degraded, degraded["c"]
    time.sleep(4 * c.hb_interval)            # a few reconciles
    for k, was in d["before"].items():
        assert counter(c, k) == was, k
    m = mon_map(c)
    assert m.osd_weight[d["victim"]] != 0
    for dm in live(c):
        assert not dm._recovering
        for ps, be in dm.backends.items():
            assert be.acting == d["acting"][ps], (ps, be.acting)
    for ps, acting in d["acting"].items():
        now = m.pg_to_up_acting_osds(1, ps)[2]
        assert [o for o in now if o in c.osd_ids()] \
            == [o for o in acting if o != d["victim"]]
