"""Repair policy plane (r17) — DownClock classification, lazy repair
deferral/cancellation, risk-ordered burst recovery, and per-domain
repair budgets.

Unit tests drive the policy objects in VIRTUAL time (now is a
parameter everywhere, the scheduler discipline), so windows expire
instantly and nothing here sleeps. The live wire-tier cells (slow:
one extra cluster boot each; the tier-1 live representative is
test_thrash.py::test_thrash_transient_smoke) prove the payoff
end-to-end: a within-window revive moves ZERO repair bytes, and the
m-1 override beats an hour-long delay."""

import pytest

from ceph_tpu.osd.repairpolicy import (DownClock, RepairPolicy,
                                       exposure_units, order_plans,
                                       plan_helper_cost, risk_key)
from ceph_tpu.osd.scheduler import DomainBudgets, TokenBucket
from ceph_tpu.utils.config import Config

UP = [True] * 6


def down(*osds):
    return [i not in osds for i in range(6)]


def make_policy(delay=10.0, **opts):
    cfg = Config()
    cfg.set("osd_repair_delay", delay)
    for k, v in opts.items():
        cfg.set(k, v)
    p = RepairPolicy(config=cfg)
    p.observe_map(UP, now=0.0)      # baseline: everyone up
    return p, cfg


# -- DownClock ----------------------------------------------------------------

def test_downclock_transitions_and_flapping():
    ck = DownClock()
    assert ck.state == DownClock.UP
    # suspicion is reversible and never starts a deferral window
    ck.mark_suspect()
    assert ck.state == DownClock.SUSPECT
    ck.clear_suspect()
    assert ck.state == DownClock.UP
    # down -> deferred; the delay elapsing confirms
    ck.mark_down(now=100.0)
    assert ck.state == DownClock.DOWN_DEFERRED
    assert not ck.maybe_confirm_elapsed(10.0, now=105.0)
    assert ck.maybe_confirm_elapsed(10.0, now=110.0)
    assert ck.state == DownClock.DOWN_CONFIRMED
    assert ck.confirmed_reason == "delay_elapsed"
    # revive returns to up; a short dwell counts a FLAP
    ck.mark_up(now=111.0, delay=10.0)
    assert ck.state == DownClock.UP and ck.flaps == 0   # dwell 11 > 10
    for i in range(3):                                   # flapping
        ck.mark_down(now=200.0 + i)
        ck.mark_up(now=200.5 + i, delay=10.0)
    assert ck.flaps == 3
    assert ck.state == DownClock.UP
    # a second mark_down while already down is a no-op (stamp kept)
    ck.mark_down(now=300.0)
    ck.mark_down(now=305.0)
    assert ck.down_since == 300.0


def test_downclock_confirm_only_from_deferred():
    ck = DownClock()
    ck.confirm("m1_override")             # up: nothing to confirm
    assert ck.state == DownClock.UP
    ck.mark_down(now=1.0)
    ck.confirm("m1_override")
    assert ck.state == DownClock.DOWN_CONFIRMED
    assert ck.confirmed_reason == "m1_override"


# -- lazy repair decisions ----------------------------------------------------

def test_defer_then_window_expiry_confirms():
    p, _ = make_policy(delay=10.0)
    p.observe_map(down(3), now=100.0)
    # inside the window: park (redundancy 3, one loss)
    assert p.should_defer(0, {3}, 1, 3, 4, now=105.0)
    assert 0 in p.parked
    assert p.counters["repair_deferred_stripes"] == 4
    # re-evaluation inside the window keeps parking, counts once
    assert p.should_defer(0, {3}, 1, 3, 4, now=108.0)
    assert p.counters["repair_deferred_stripes"] == 4
    # window expired: plan now, parked record dropped
    assert not p.should_defer(0, {3}, 1, 3, 4, now=110.0)
    assert 0 not in p.parked
    assert p.counters["repair_deferred_confirmed"] == 1
    assert p.clocks[3].state == DownClock.DOWN_CONFIRMED


def test_revive_cancels_parked_and_queues_recheck():
    p, _ = make_policy(delay=10.0)
    p.observe_map(down(3), now=100.0)
    assert p.should_defer(0, {3}, 1, 3, 4, now=101.0)
    assert p.should_defer(1, {3}, 1, 3, 2, now=101.5)
    revived = p.observe_map(UP, now=104.0)
    assert revived == [3]
    assert not p.parked                    # both PGs cancelled
    assert p.counters["repair_deferred_cancelled"] == 2
    assert p.take_recheck(0) == {3}
    assert p.take_recheck(1) == {3}
    assert p.take_recheck(0) == set()      # consumed once
    assert p.clocks[3].flaps == 1          # dwell 4 < delay 10
    # the re-check outcome feeds the counters the thrasher asserts
    p.note_recheck(0)
    p.note_recheck(5)
    assert p.counters["repair_cancel_noop"] == 1
    assert p.counters["repair_catchup_objects"] == 5


def test_m1_override_beats_delay():
    p, _ = make_policy(delay=3600.0)       # an hour of patience
    p.observe_map(down(2, 3), now=10.0)
    # redundancy 3, TWO losses -> 1 left: the delay loses immediately
    assert not p.should_defer(0, {2, 3}, 2, 3, 4, now=11.0)
    assert p.counters["repair_urgent_overrides"] == 1
    assert p.counters["repair_urgent_parked"] == 0
    # the holders are confirmed: a SINGLE-loss stripe of the same OSD
    # must not re-enter deferral afterwards
    assert not p.should_defer(1, {2}, 1, 3, 4, now=12.0)
    # m=1 codes are always urgent (any loss leaves zero redundancy)
    p2, _ = make_policy(delay=3600.0)
    p2.observe_map(down(1), now=0.0)
    assert not p2.should_defer(0, {1}, 1, 1, 4, now=1.0)


def test_stripe_budget_confirms_early():
    p, _ = make_policy(delay=3600.0,
                       osd_repair_deferred_max_stripes=10)
    p.observe_map(down(3), now=0.0)
    assert p.should_defer(0, {3}, 1, 3, 8, now=1.0)     # 8 parked
    # 8 + 6 > 10: the budget confirms instead of parking more
    assert not p.should_defer(1, {3}, 1, 3, 6, now=1.5)
    assert p.clocks[3].state == DownClock.DOWN_CONFIRMED
    assert p.counters["repair_deferred_confirmed"] == 1


def test_unknown_down_at_boot_is_eager():
    """A restarted primary cannot date a peer's down window — its
    FIRST map marks already-down peers confirmed (deferring an
    unknowable window would gamble safety on a guess)."""
    p = RepairPolicy(config=Config())
    p._config.set("osd_repair_delay", 3600.0)
    p.observe_map(down(4), now=0.0)        # first observation
    assert p.clocks[4].state == DownClock.DOWN_CONFIRMED
    assert p.clocks[4].confirmed_reason == "unknown_down_at_boot"
    assert not p.should_defer(0, {4}, 1, 3, 4, now=1.0)


def test_admin_out_confirms():
    p, _ = make_policy(delay=3600.0)
    p.observe_map(down(3), out_osds=[3], now=5.0)
    assert p.clocks[3].state == DownClock.DOWN_CONFIRMED
    assert p.clocks[3].confirmed_reason == "marked_out"


def test_live_config_reresolution():
    """The new options resolve AT CALL TIME through the layered
    Config — a committed `config set` retunes a running policy with
    no restart (the md_config_obs_t property the daemon relies on)."""
    p, cfg = make_policy(delay=0.0)
    p.observe_map(down(3), now=0.0)
    assert not p.should_defer(0, {3}, 1, 3, 4, now=1.0)   # policy off
    cfg.set("osd_repair_delay", 50.0)                     # turn it on
    assert p.should_defer(0, {3}, 1, 3, 4, now=2.0)
    cfg.set("osd_repair_delay", 0.0, level="override")    # off again
    assert not p.should_defer(0, {3}, 1, 3, 4, now=3.0)
    assert p.queue_order == "risk"
    cfg.set("osd_repair_queue_order", "pgid")
    assert p.queue_order == "pgid"


def test_exposure_time_accounting():
    p, _ = make_policy()
    p.note_exposure(0, True, now=10.0)
    p.note_exposure(0, True, now=11.0)     # steady state: no re-stamp
    assert p.exposed_pgs() == 1
    p.note_exposure(0, False, now=12.5)
    assert p.exposed_pgs() == 0
    assert p.counters["repair_time_at_m1_ms"] == 2500
    p.note_exposure(1, False, now=13.0)    # never exposed: no-op
    assert p.counters["repair_time_at_m1_ms"] == 2500


# -- risk ordering + exposure accounting -------------------------------------

class _FakePlan:
    def __init__(self, lost, helpers, wire_fraction=1.0):
        self.lost = list(lost)
        self.helper = list(helpers)
        if wire_fraction < 1.0:
            class _R:
                pass
            self.repair = _R()
            self.repair.wire_fraction = wire_fraction
        else:
            self.repair = None


def test_risk_key_and_order_plans():
    m = 3
    entries = [
        (0, _FakePlan([1], range(8)), set()),        # redundancy 2
        (1, _FakePlan([1, 2], range(8)), set()),     # redundancy 1 !
        (2, _FakePlan([1], range(4)), set()),        # red 2, cheaper
    ]

    def red(ps, plan):
        return m - len(plan.lost)

    ordered = order_plans(entries, red, mode="risk")
    assert [e[0] for e in ordered] == [1, 2, 0]
    # pgid mode keeps id order but COUNTS the inversions it ships
    counts = {}
    ordered_pg = order_plans(
        entries, red, mode="pgid",
        counter=lambda k, n: counts.__setitem__(
            k, counts.get(k, 0) + n))
    assert [e[0] for e in ordered_pg] == [0, 1, 2]
    assert counts["repair_risk_inversions"] == 1    # pg0 before pg1
    # risk mode ships zero inversions by construction
    counts2 = {}
    order_plans(entries, red, mode="risk",
                counter=lambda k, n: counts2.__setitem__(k, n))
    assert not counts2
    # the r14 cost tie-break: sub-chunk plans are cheaper than
    # full-row plans with the same helper count
    assert plan_helper_cost(_FakePlan([1], range(8), 0.25)) \
        < plan_helper_cost(_FakePlan([1], range(8)))
    assert risk_key(1, 2.0, 9) < risk_key(2, 1.0, 0)


def test_exposure_units_risk_vs_pgid():
    """The accounting metric BENCH_r17's rack-loss cell pins: with a
    few at-m-1 stripes buried late in PG-id order, risk order cuts
    cumulative exposure by well over half (exposed stripes complete
    first, so they stop accumulating while the bulk rebuilds)."""
    stripes = [(ps, 100.0, ps >= 28) for ps in range(32)]  # 4 at m-1
    pgid = exposure_units(stripes)
    risk = exposure_units(sorted(stripes, key=lambda s: not s[2]))
    assert risk < 0.5 * pgid
    assert exposure_units([]) == 0.0


# -- domain budgets -----------------------------------------------------------

def test_token_bucket_refill_and_debt():
    b = TokenBucket(rate=100.0, burst=200.0, now=0.0)
    assert b.take(150.0, now=0.0) == 0.0        # burst covers it
    w = b.take(100.0, now=0.0)                  # 50 left: wait 0.5s
    assert w == pytest.approx(0.5)
    assert b.take(100.0, now=1.0) == 0.0        # refilled 100 -> 150
    # an oversized cost clears from a FULL bucket (debt), then the
    # next grant throttles — no deadlock on one huge batch
    big = TokenBucket(rate=100.0, burst=100.0, now=0.0)
    assert big.take(500.0, now=0.0) == 0.0
    assert big.take(1.0, now=0.0) > 0.0
    big.retune(rate=1000.0, burst=50.0)
    assert big.tokens <= 50.0


def test_domain_budgets_starvation_freedom():
    """One rack draining its budget to zero must not delay another
    rack's grants — the property that keeps a burst rebuild in rack A
    from freezing rack B's repairs (both domains make progress)."""
    d = DomainBudgets()
    rate, burst = 1e6, 2e6
    # rack A pulls its whole burst, then throttles
    assert d.request({"rackA": 2e6}, rate, burst, now=0.0) == 0.0
    wait_a = d.request({"rackA": 1e6}, rate, burst, now=0.0)
    assert wait_a > 0.0
    # rack B still grants at the same instant
    assert d.request({"rackB": 1e6}, rate, burst, now=0.0) == 0.0
    # a two-domain pull is all-or-nothing: the grantable domain is
    # REFUNDED when the other refuses, so no tokens leak
    before = d._buckets["rackB"].tokens
    wait_ab = d.request({"rackA": 1e6, "rackB": 0.5e6}, rate, burst,
                        now=0.0)
    assert wait_ab > 0.0
    assert d._buckets["rackB"].tokens == pytest.approx(before)
    # after the refill interval rack A proceeds: progress, not
    # starvation
    assert d.request({"rackA": 1e6}, rate, burst,
                     now=wait_a + 0.01) == 0.0
    dump = d.dump()
    assert dump["rackA"]["throttled"] >= 2


def test_crush_domain_of():
    from ceph_tpu.crush.map import build_hierarchy
    m = build_hierarchy(16, osds_per_host=2, hosts_per_rack=2)
    r0 = m.domain_of(0)
    assert m.buckets[r0].type_id == 2               # a rack
    assert m.domain_of(3) == r0                     # same rack (4/host-pair)
    assert m.domain_of(15) != r0
    # flat fallback: no rack tier -> the highest ancestor is the key
    # (budgets degrade to one global bucket instead of exploding)
    from ceph_tpu.crush.map import CrushMap
    flat = CrushMap()
    flat.add_type(1, "host")
    flat.add_bucket(-1, 1, "straw2", [0, 1, 2])
    assert flat.domain_of(0) == flat.domain_of(2) == -1


# -- health -------------------------------------------------------------------

def test_health_pg_exposed():
    from ceph_tpu.mgr.health import HEALTH_WARN, health_checks

    class _Reports:
        def totals(self):
            return {"slow_ops": 0}

        def pg_states(self):
            return {"1.0": "active+degraded+exposed",
                    "1.1": "active+clean"}

        def daemons(self):
            return {}

        def report_ages(self):
            return {}

    h = health_checks(reports=_Reports())
    codes = {c["code"]: c for c in h["checks"]}
    assert "PG_EXPOSED" in codes
    assert codes["PG_EXPOSED"]["severity"] == HEALTH_WARN
    assert "1.0" in codes["PG_EXPOSED"]["detail"][0]
    assert h["status"] == HEALTH_WARN


# -- live wire tier (slow: one cluster boot each; the tier-1 live
# representative is the thrasher's transient smoke cell) ----------------------

@pytest.mark.slow
def test_lazy_repair_live_revive_cancels_with_zero_bytes():
    """End-to-end payoff on the wire tier (cephx off, small objects):
    kill an OSD, let the policy park the rebuild, revive inside the
    window — the cancel is a cursor re-check and the cluster-wide
    repair counters (decode rebuilds + helper pulls + backfill
    copies) move ZERO bytes. Then flip the delay live and watch the
    m-1 override beat it."""
    import time as _t

    from ceph_tpu.osd.standalone import StandaloneCluster
    c = StandaloneCluster(
        n_osds=8, profile="plugin=tpu_rs k=2 m=3",
        pg_num=2, hb_interval=0.25, hb_grace=1.2)
    try:
        cl = c.client()
        cl.config_set("osd_repair_delay", 30.0)
        cl.write({f"o{i}": bytes([i]) * 300 for i in range(8)})
        c.wait_for_clean(timeout=60)

        def repair_bytes():
            return sum(d.ec_perf.get("recovered_bytes")
                       + d.ec_perf.get("recover_wire_bytes")
                       + d.perf.get("move_bytes")
                       for d in c.osds.values()
                       if not d._stop.is_set())

        def policy(key):
            return sum(d.repair_policy.counters.get(key, 0)
                       for d in c.osds.values()
                       if not d._stop.is_set())

        b0 = repair_bytes()
        victim = 7
        c.kill_osd(victim)
        c.wait_for_down(victim, timeout=30)
        deadline = _t.monotonic() + 20
        while _t.monotonic() < deadline:
            if policy("repair_deferred_stripes") > 0:
                break
            _t.sleep(0.2)
        assert policy("repair_deferred_stripes") > 0
        assert repair_bytes() == b0         # parked: nothing moved
        c.revive_osd(victim)
        c.wait_for_clean(timeout=60)
        _t.sleep(1.0)
        assert repair_bytes() == b0, \
            "within-window revive moved repair bytes"
        assert policy("repair_deferred_cancelled") >= 1
        assert policy("repair_cancel_noop") >= 1

        # live re-resolution + m-1 override: a 1-hour delay loses to
        # a second failure in the same PG
        cl.config_set("osd_repair_delay", 3600.0)
        d0 = next(d for d in c.osds.values()
                  if not d._stop.is_set() and d.backends)
        be = next(iter(d0.backends.values()))
        v1, v2 = [o for o in be.acting if o != d0.osd_id][:2]
        c.kill_osd(v1)
        c.kill_osd(v2)
        c.wait_for_down(v1, timeout=30)
        c.wait_for_down(v2, timeout=30)
        c.wait_for_clean(timeout=90)        # rebuilds NOW, not in 1h
        assert policy("repair_urgent_overrides") >= 1
        assert policy("repair_urgent_parked") == 0
        assert repair_bytes() > b0
        # the data survived the whole dance bit-exact
        for i in range(8):
            assert cl.read(f"o{i}") == bytes([i]) * 300
    finally:
        c.shutdown()
