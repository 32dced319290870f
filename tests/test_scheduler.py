"""Throttle + mClock scheduler tests (ref behaviors: src/common/
Throttle.cc gtests; mClock QoS properties — reservation floor, weight
sharing, limit ceiling — per the dmclock design the reference wraps)."""

import threading
import time

import pytest

from ceph_tpu.osd.scheduler import (ClientProfile, MClockScheduler)
from ceph_tpu.utils.throttle import Throttle


class TestThrottle:
    def test_basic_get_put(self):
        t = Throttle("t", 10)
        assert t.get(4)
        assert t.get(6)
        assert t.get_current() == 10
        assert not t.get_or_fail(1)
        assert t.put(6) == 4
        assert t.get_or_fail(1)

    def test_zero_max_disables(self):
        t = Throttle("t", 0)
        for _ in range(100):
            assert t.get_or_fail(1000)
        assert t.get(10**9)

    def test_oversized_request_admitted_alone(self):
        t = Throttle("t", 5)
        assert t.get(3)
        got = []

        def worker():
            got.append(t.get(8, timeout=5.0))
        th = threading.Thread(target=worker)
        th.start()
        time.sleep(0.05)
        assert not got          # blocked: 3 held, 8 > max
        t.put(3)                # drains to 0 -> oversized admitted
        th.join(5.0)
        assert got == [True]
        assert t.get_current() == 8

    def test_fifo_no_starvation(self):
        t = Throttle("t", 10)
        assert t.get(9)
        order = []

        def big():
            t.get(8, timeout=5.0)
            order.append("big")

        def small():
            t.get(1, timeout=5.0)
            order.append("small")
        b = threading.Thread(target=big)
        b.start()
        time.sleep(0.05)
        s = threading.Thread(target=small)
        s.start()
        time.sleep(0.05)
        # small would fit (9+1<=10) but big is ahead in FIFO
        assert order == []
        t.put(9)
        b.join(5.0)
        s.join(5.0)
        assert order == ["big", "small"]

    def test_get_timeout(self):
        t = Throttle("t", 2)
        assert t.get(2)
        t0 = time.perf_counter()
        assert not t.get(1, timeout=0.1)
        assert time.perf_counter() - t0 < 2.0
        t.put(2)
        assert t.get(1)  # waiter list cleaned up after timeout

    def test_put_more_than_held_raises(self):
        t = Throttle("t", 5)
        t.get(2)
        with pytest.raises(ValueError):
            t.put(3)

    def test_reset_max_wakes(self):
        t = Throttle("t", 2)
        t.get(2)
        got = []

        def worker():
            got.append(t.get(2, timeout=5.0))
        th = threading.Thread(target=worker)
        th.start()
        time.sleep(0.05)
        t.reset_max(10)
        th.join(5.0)
        assert got == [True]


def run_sim(sched: MClockScheduler, feeders: dict[str, int],
            seconds: float = 2.0, capacity_per_s: float = 1000.0,
            dt: float = 0.001) -> dict[str, int]:
    """Keep every class saturated with `feeders[cls]` queued ops; pump
    at `capacity_per_s`; count ops served per class."""
    served = {c: 0 for c in feeders}
    now = 0.0
    budget_per_step = capacity_per_s * dt
    carry = 0.0
    while now < seconds:
        for cls, depth in feeders.items():
            # top the queue back up (saturation)
            backlog = sum(1 for q in [sched._classes[cls]]
                          for _ in q.items)
            for _ in range(depth - backlog):
                sched.enqueue(cls, object())
        carry += budget_per_step
        while carry >= 1.0:
            got = sched.dequeue(now)
            if got is None:
                break
            served[got[0]] += 1
            carry -= 1.0
        now += dt
    return served


class TestMClock:
    def test_weight_proportional_share(self):
        s = MClockScheduler({
            "a": ClientProfile(weight=3.0),
            "b": ClientProfile(weight=1.0),
        })
        served = run_sim(s, {"a": 10, "b": 10}, seconds=1.0,
                         capacity_per_s=400.0)
        ratio = served["a"] / max(1, served["b"])
        assert 2.4 < ratio < 3.6, served

    def test_reservation_floor_under_pressure(self):
        # low-weight class with a 100/s reservation must still get
        # ~100/s although the heavy class would otherwise take ~all
        s = MClockScheduler({
            "heavy": ClientProfile(weight=100.0),
            "floor": ClientProfile(reservation=100.0, weight=0.001),
        })
        served = run_sim(s, {"heavy": 20, "floor": 20}, seconds=2.0,
                         capacity_per_s=500.0)
        assert served["floor"] >= 190, served   # ~100/s over 2s
        assert served["heavy"] >= 700, served   # rest of capacity

    def test_limit_ceiling(self):
        s = MClockScheduler({
            "capped": ClientProfile(weight=10.0, limit=50.0),
        })
        served = run_sim(s, {"capped": 50}, seconds=2.0,
                         capacity_per_s=1000.0)
        assert served["capped"] <= 110, served  # ~50/s over 2s

    def test_spare_capacity_goes_to_unlimited(self):
        s = MClockScheduler({
            "capped": ClientProfile(weight=10.0, limit=50.0),
            "open": ClientProfile(weight=1.0),
        })
        served = run_sim(s, {"capped": 50, "open": 50}, seconds=1.0,
                         capacity_per_s=1000.0)
        assert served["capped"] <= 60, served
        assert served["open"] >= 900, served

    def test_idle_class_does_not_bank_credit(self):
        s = MClockScheduler({
            "capped": ClientProfile(weight=1.0, limit=100.0),
        })
        # idle from t=0..10, then saturate for 0.5s: must get ~50 ops,
        # not 10s * 100/s of banked burst
        for _ in range(2000):
            s.enqueue("capped", object())
        served = 0
        now = 10.0
        while now < 10.5:
            while s.dequeue(now) is not None:
                served += 1
            now += 0.001
        assert served <= 60, served

    @pytest.mark.parametrize("lag_s", [0.5, 5.0, 50.0])
    def test_a_class_back_from_idle_joins_the_busy_classes_virtual_time(
            self, lag_s):
        """An overloaded shard: a recovery round that re-enqueues itself
        is served at 4 grants/s against a reservation of 12.5, so its R
        tags run `lag_s` behind the clock. A client whose queue ran
        empty comes back: its ops are served in the reservations' ratio
        (50 : 25/2, four ops a grant) from the first one on, however
        long the round has run: it does not wait out the round's
        credit."""
        s = MClockScheduler({
            "client": ClientProfile(reservation=50.0, weight=10.0),
            "background_recovery": ClientProfile(reservation=25.0,
                                                 weight=5.0, limit=100.0)})
        now = 100.0
        s.enqueue("background_recovery", "grant", cost=2.0)
        while now < 100.0 + lag_s / (1 - 0.08 / 0.25):
            assert s.dequeue(now) == ("background_recovery", "grant")
            now += 0.25                      # a grant's service time
            s.enqueue("background_recovery", "grant", cost=2.0)
        q = s._classes["background_recovery"]
        assert now - q.r_prev >= lag_s * 0.9
        for i in range(12):
            s.enqueue("client", i)
        order = []
        while len([x for x in order if x != "grant"]) < 12:
            cls, item = s.dequeue(now)
            order.append(item)
            now += 0.25 if item == "grant" else 0.1
            if item == "grant":
                s.enqueue("background_recovery", "grant", cost=2.0)
        # the first client op waits for no grant, and no client op
        # waits behind more than one
        assert order[0] == 0
        assert "grant" in order
        assert all(not (a == "grant" and b == "grant")
                   for a, b in zip(order, order[1:]))
        assert order.count("grant") <= 4

    def test_fifo_within_class(self):
        s = MClockScheduler({"c": ClientProfile(weight=1.0)})
        for i in range(5):
            s.enqueue("c", i)
        got = [s.dequeue(float(i))[1] for i in range(5)]
        assert got == [0, 1, 2, 3, 4]

    def test_unknown_class_raises(self):
        s = MClockScheduler()
        with pytest.raises(KeyError):
            s.enqueue("nope", object())

    def test_default_profiles_recovery_vs_client(self):
        s = MClockScheduler()  # DEFAULT_PROFILES
        served = run_sim(s, {"client": 50, "background_recovery": 50},
                         seconds=1.0, capacity_per_s=400.0)
        # recovery makes progress (reservation floor) but clients
        # dominate (weight 10 vs 5, recovery limited to 100/s)
        assert served["background_recovery"] >= 25
        assert served["background_recovery"] <= 120
        assert served["client"] > served["background_recovery"]


class TestReviewRegressions:
    def test_timeout_head_passes_baton(self):
        # head waiter timing out must wake the next waiter if it fits
        t = Throttle("t", 10)
        assert t.get(8)
        got = []

        def big():
            got.append(("big", t.get(5, timeout=0.15)))

        def small():
            got.append(("small", t.get(2, timeout=5.0)))
        b = threading.Thread(target=big)
        b.start()
        time.sleep(0.05)
        s = threading.Thread(target=small)
        s.start()
        b.join(5.0)   # big times out (8+5>10)
        s.join(5.0)   # small (8+2<=10) must be woken by the departure
        assert ("big", False) in got
        assert ("small", True) in got

    def test_remove_if_purges_cancelled_ops(self):
        s = MClockScheduler({"c": ClientProfile(weight=1.0, limit=10.0)})
        for i in range(20):
            s.enqueue("c", ("pg1", i))
        for i in range(3):
            s.enqueue("c", ("pg2", i))
        assert s.remove_if("c", lambda op: op[0] == "pg1") == 20
        assert len(s) == 3
        got = [s.dequeue(100.0 + i) for i in range(3)]
        assert [g[1][0] for g in got] == ["pg2"] * 3
        assert s.dequeue(200.0) is None


class TestRound10Additions:
    def test_next_eligible_limit_bound(self):
        s = MClockScheduler({
            "capped": ClientProfile(weight=1.0, limit=10.0)})
        for _ in range(3):
            s.enqueue("capped", object())
        assert s.next_eligible(0.0) == 0.0      # head servable now
        assert s.dequeue(0.0) is not None
        # head now spaced by 1/limit: eligible ~0.1s out, not "poll me
        # every tick"
        t = s.next_eligible(0.0)
        assert t is not None and 0.05 < t <= 0.11
        assert s.next_eligible(1.0) == 1.0      # past the spacing
        s.dequeue(1.0)
        s.dequeue(2.0)
        assert s.next_eligible(3.0) is None     # empty queue

    def test_dump_counts_grants(self):
        s = MClockScheduler()
        for i in range(4):
            s.enqueue("client", i, cost=2.0)
        s.enqueue("background_recovery", "r", cost=5.0)
        for t in range(3):
            s.dequeue(float(t))
        d = s.dump()
        assert sum(c["served"] for c in d.values()) == 3
        assert sum(c["queued"] for c in d.values()) == 2
        assert d["client"]["profile"]["weight"] == 10.0
        served_cost = sum(c["served_cost"] for c in d.values())
        assert served_cost > 0


class TestPerTenantClasses:
    """Round-11: dynamic per-tenant (ρ, w, λ) classes keyed by client
    entity (ensure_class + the osd_mclock_scheduler_tenant_* config
    grammar) — one heavy tenant must not starve the rest."""

    def test_parse_profile_and_table(self):
        from ceph_tpu.osd.scheduler import (parse_profile,
                                            parse_profile_table)
        p = parse_profile(" 50, 10 , 0 ")
        assert (p.reservation, p.weight, p.limit) == (50.0, 10.0, 0.0)
        table = parse_profile_table(
            "client.a=1,2,3;client.b=0,5,0;")
        assert set(table) == {"client.a", "client.b"}
        assert table["client.a"].limit == 3.0
        with pytest.raises(ValueError):
            parse_profile("1,2")          # not three fields
        with pytest.raises(ValueError):
            parse_profile_table("justanentity")   # no '='
        with pytest.raises(ValueError):
            parse_profile("5,1,3")        # reservation > limit

    def test_ensure_class_creates_then_retunes(self):
        s = MClockScheduler()
        s.ensure_class("tenant:a", ClientProfile(weight=2.0))
        s.enqueue("tenant:a", "op")
        assert s.dequeue(0.0) == ("tenant:a", "op")
        # retune in place: profile changes, queue/order survive
        s.enqueue("tenant:a", "op2")
        s.ensure_class("tenant:a", ClientProfile(weight=9.0))
        assert s.dump()["tenant:a"]["profile"]["weight"] == 9.0
        assert s.dequeue(1.0) == ("tenant:a", "op2")
        # idempotent for an unchanged profile
        s.ensure_class("tenant:a", ClientProfile(weight=9.0))
        assert "tenant:a" in s.class_names()

    def test_tenant_weight_split_under_saturation(self):
        # two tenants sharing spare capacity 4:1 by weight — the
        # "heavy tenant cannot starve the rest" property in its
        # simplest measurable form
        s = MClockScheduler({
            "tenant:heavy": ClientProfile(weight=4.0),
            "tenant:light": ClientProfile(weight=1.0),
        })
        served = run_sim(s, {"tenant:heavy": 10, "tenant:light": 10},
                         seconds=1.0, capacity_per_s=500.0)
        ratio = served["tenant:heavy"] / max(1, served["tenant:light"])
        assert 3.2 < ratio < 4.8, served

    def test_tenant_limit_caps_hedge_storms(self):
        # a tenant flooding duplicates under a λ cap cannot exceed its
        # ceiling; an unlimited tenant soaks the rest
        s = MClockScheduler({
            "tenant:storm": ClientProfile(weight=10.0, limit=50.0),
            "tenant:calm": ClientProfile(weight=1.0),
        })
        served = run_sim(s, {"tenant:storm": 50, "tenant:calm": 50},
                         seconds=1.0, capacity_per_s=1000.0)
        assert served["tenant:storm"] <= 60, served
        assert served["tenant:calm"] >= 900, served
