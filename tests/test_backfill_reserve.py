"""Backfill reservation (`osd_max_backfills`; ref: OSD::local_reserver /
remote_reserver and the MBackfillReserve exchange) and the size of a
recovery grant, on the wire tier: real daemons, real frames.

Two kinds of case. The reserver alone, with stub plans whose settle
waits on a gate, so that who holds a target's slot and who queues is
the test's to say: several primaries and one target, priority order,
the slot freed by a dead primary and by a map that drops the target.
And a real failure on a small cephx pool (k=4 m=2, 7 OSDs, 8 PGs,
64 KiB objects): nothing is built while a PG is only degraded, a PG
asks for its reservation only once its program is ready, no target
ever holds more PGs than `osd_max_backfills`, no grant stages more than
`osd_recovery_max_active x osd_recovery_max_chunk`, a PG that waits for
its reservation serves reads and writes, and a write that lands between
an object's stage and its writeback is skipped, grant after grant.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.chaos import load_factor
from ceph_tpu.osd import standalone
from ceph_tpu.osd.ecbackend import (ECBackend, RecoveryRunner, ShardSet,
                                    shard_cid)
from ceph_tpu.osd.standalone import (MBackfillReserve, StandaloneCluster,
                                     _Backfill)

_LF = load_factor()
K, M = 4, 2
SIZE = 64 << 10
ROW = SIZE // K
PROFILE = f"plugin=jerasure technique=reed_sol_van k={K} m={M}"


def make(**kw):
    c = StandaloneCluster(n_osds=K + M + 1, pg_num=8, profile=PROFILE,
                          op_timeout=3.0, chunk_size=256,
                          down_out_interval=600.0, hb_interval=0.5,
                          hb_grace=30.0, **kw)
    c.wait_for_clean(timeout=30 * _LF)
    return c


def live(c):
    return [d for d in c.osds.values() if not d._stop.is_set()]


def acting_of(c):
    osdmap = max((m.osdmap for m in c.mons if m.osdmap is not None),
                 key=lambda m: m.epoch)
    return {ps: [int(o) for o in osdmap.pg_to_up_acting_osds(1, ps)[2]]
            for ps in range(c.pg_num)}


def set_everywhere(c, cl, key, value):
    cl.config_set(key, str(value))
    c._wait(lambda: all(str(d.config[key]) == str(value) for d in live(c)),
            15 * _LF, f"{key} on every daemon")


# -- the wire frame ---------------------------------------------------------

@pytest.mark.parametrize("op", [MBackfillReserve.REQUEST,
                                MBackfillReserve.GRANT,
                                MBackfillReserve.RELEASE])
def test_the_frame_round_trips(op):
    from ceph_tpu.utils.encoding import Decoder, Encoder
    msg = MBackfillReserve(op, 7, 3, epoch=41, prio=(2, 8.0))
    e = Encoder()
    msg.encode_payload(e)
    back = MBackfillReserve.decode_payload(Decoder(e.bytes()))
    assert (back.op, back.ps, back.primary, back.epoch, back.prio) \
        == (op, 7, 3, 41, (2, 8.0))


# -- the reserver alone: stub plans -------------------------------------------

class _StubPlan:
    """A plan with nothing to rebuild whose settle waits on a gate: its
    round holds the PG's reservations until the test opens it."""

    lost, helper, names_by_len, dec_fn = [0], [], {}, None

    def __init__(self):
        self.gate, self.ran = threading.Event(), threading.Event()

    def finish(self):
        self.ran.set()
        self.gate.wait(30 * _LF)


def plant(d, ps, target, prio):
    """PG `ps` planned on daemon `d` with its program built, one target."""
    bf = _Backfill(ps, _StubPlan(), set(), prio, int(d.osdmap.epoch),
                   {target})
    bf.state = "ready"
    d._recovering[ps] = bf
    d._backfill_pump()
    return bf


def one_target_four_primaries(c):
    """The OSD in the most PGs, four of its PGs, and four other daemons
    to stand as their primaries (none the PG's real primary, so that no
    reconcile of theirs looks at the planted PG)."""
    acting = acting_of(c)
    target = max(c.osd_ids(), key=lambda o: sum(o in a for a in acting.values()))
    pgs = [ps for ps, a in acting.items() if target in a][:4]
    assert len(pgs) == 4
    primaries = []
    for ps in pgs:
        primaries.append(next(
            d for d in live(c) if d.osd_id != target
            and d.osd_id != acting[ps][0] and d not in primaries))
    return c.osds[target], pgs, primaries


@pytest.fixture
def pool():
    c = make()
    try:
        yield c
    finally:
        for d in c.osds.values():        # let every stub round end
            for bf in list(d._recovering.values()):
                if isinstance(bf, _Backfill) \
                        and isinstance(bf.plan, _StubPlan):
                    bf.plan.gate.set()
        c.shutdown()


@pytest.mark.parametrize("cap,order", [(1, "ACDB"), (2, "ABCD")])
def test_a_target_takes_osd_max_backfills_pgs_in_priority_order(
        pool, cap, order):
    """Four primaries, one target. A arrives alone and is granted; B, C
    and D arrive with priorities 2, 0 and 1. With one slot they are
    served C, D, B as the slot comes back; with two B found a slot
    free, and C then D take the slots A and B give back."""
    c = pool
    if cap != 1:
        set_everywhere(c, c.client(), "osd_max_backfills", cap)
    target, pgs, primaries = one_target_four_primaries(c)
    prios = {"A": (5, 0.0), "B": (2, 0.0), "C": (0, 0.0), "D": (1, 0.0)}
    bfs = {}
    for tag, ps, d in zip("ABCD", pgs, primaries):
        bfs[tag] = plant(d, ps, target.osd_id, prios[tag])
        if tag == "A":
            assert bfs["A"].plan.ran.wait(20 * _LF)
        else:               # each request is at the target before the next
            c._wait(lambda: (d.osd_id, ps) in target._bf_held
                    or (d.osd_id, ps) in target._bf_queued,
                    10 * _LF, f"{tag}'s request at the target")
    assert len(target._bf_held) == cap
    assert len(target._bf_queued) == 4 - cap
    served, opened = "", 0
    while opened < 4:
        if len(served) < 4 and opened + cap > len(served):
            c._wait(lambda: any(bf.plan.ran.is_set() and tag not in served
                                for tag, bf in bfs.items()),
                    20 * _LF, "the next PG's round runs")
            time.sleep(0.2)              # whoever else was let in with it
        now = sorted(tag for tag, bf in bfs.items()
                     if bf.plan.ran.is_set() and tag not in served)
        assert len(served) + len(now) - opened <= cap, (served, now)
        served += "".join(now)
        # a waiting PG stays planned and degraded, never failed
        for tag, bf in bfs.items():
            if tag not in served:
                assert bf.state == "reserving" and not bf.failed
        first = bfs[served[opened]]      # the oldest round still held
        first.plan.gate.set()            # settles and releases
        c._wait(lambda: first.state == "done", 20 * _LF,
                "the round settled")
        opened += 1
    assert served == order
    c._wait(lambda: not target._bf_held and not target._bf_queued,
            10 * _LF, "every slot given back")
    assert int(target.perf.get("backfills_active_max")) == cap
    assert int(target.perf.get("backfill_reservations_granted")) == 4
    assert int(target.perf.get("backfill_reservation_waits")) == 4 - cap
    for d, ps in zip(primaries, pgs):
        assert ps not in d._recovering


@pytest.mark.parametrize("how", ["primary_dies", "map_drops_target"])
def test_a_target_frees_a_slot_nobody_will_release(pool, how):
    """The primary dies holding the slot; or a map (the target marked
    out) drops the target from the PG's acting set: the next reconcile
    of the target gives the slot to whoever waits."""
    c = pool
    cl = c.client()
    target, pgs, primaries = one_target_four_primaries(c)
    holder = plant(primaries[0], pgs[0], target.osd_id, (0, 0.0))
    assert holder.plan.ran.wait(20 * _LF)
    waiter = plant(primaries[1], pgs[1], target.osd_id, (1, 0.0))
    key = (primaries[0].osd_id, pgs[0])
    c._wait(lambda: key in target._bf_held
            and (primaries[1].osd_id, pgs[1]) in target._bf_queued,
            10 * _LF, "one holds, one queues")
    assert not waiter.plan.ran.is_set()
    if how == "primary_dies":
        c.kill_osd(primaries[0].osd_id)
        cl.osd_down(primaries[0].osd_id)
        assert waiter.plan.ran.wait(60 * _LF)
        assert key not in target._bf_held
    else:
        cl.osd_out(target.osd_id)
        c._wait(lambda: key not in target._bf_held
                and (primaries[1].osd_id, pgs[1]) not in target._bf_queued
                and (primaries[1].osd_id, pgs[1]) not in target._bf_held,
                60 * _LF, "the target dropped what the map took from it")
    assert int(target.perf.get("backfills_active_max")) == 1


def test_a_grant_nobody_waits_for_is_handed_back(pool):
    """A GRANT for a PG this primary does not (or no longer) plan
    answers with a RELEASE, so the target's slot cannot leak."""
    c = pool
    target, pgs, primaries = one_target_four_primaries(c)
    d = primaries[0]
    with target._bf_lock:
        target._bf_held[(d.osd_id, pgs[0])] = (0, 0.0, 0)
    target._bf_send_grants([(d.osd_id, pgs[0])])
    c._wait(lambda: (d.osd_id, pgs[0]) not in target._bf_held, 10 * _LF,
            "the stray grant came back as a release")


# -- a real failure ---------------------------------------------------------

N_OBJECTS = 64
MAX_ACTIVE, MAX_CHUNK = 3, 64 << 10     # 192 KiB a grant: 2 objects of 64 KiB


def corpus(seed, n=N_OBJECTS):
    rng = np.random.default_rng(seed)
    return {f"bf-{seed}-{i}": rng.integers(0, 256, SIZE, np.uint8).tobytes()
            for i in range(n)}


def ec_sum(c, key):
    return sum(int(d.ec_perf.get(key)) for d in live(c))


def non_primary_victim(c):
    acting = acting_of(c)
    primaries = {a[0] for a in acting.values()}
    return max((o for o in c.osd_ids() if o not in primaries),
               key=lambda o: (sum(o in a[:K] for a in acting.values()), -o))


@pytest.fixture(scope="module")
def backfilled():
    """One run of the failure, observed as it goes: the pool filled,
    the victim killed and marked down (nothing may be built), then out
    with every REQUEST a primary sends recorded beside the state of its
    PG and the programs its daemon had ready; reads and writes of a PG
    that waits for its reservation; then clean."""
    c = make(cephx=True, secret=b"backfill reserve key" * 2, store="tin")
    try:
        cl = c.client()
        objs = corpus(34)
        cl.write(objs)
        c.wait_for_clean(timeout=30 * _LF)
        set_everywhere(c, cl, "osd_recovery_max_chunk", MAX_CHUNK)
        set_everywhere(c, cl, "osd_recovery_sleep", 0.05)
        victim = non_primary_victim(c)
        old = acting_of(c)
        c.kill_osd(victim)
        cl.osd_down(victim)
        c._wait(lambda: all(not d.osdmap.osd_up[victim] for d in live(c)),
                20 * _LF, "every map shows the victim down")
        time.sleep(1.5)                   # two reconciles of every daemon
        degraded = {"ready": ec_sum(c, "recover_programs_ready"),
                    "pending": ec_sum(c, "recover_programs_pending"),
                    "launches": ec_sum(c, "recover_launches"),
                    "recovering": sum(len(d._recovering) for d in live(c)),
                    "read": cl.read("bf-34-0") == objs["bf-34-0"]}

        requests, lock = [], threading.Lock()
        orig = standalone.OSDDaemon._backfill_send

        def recording(self, target, op, bf):
            if op == MBackfillReserve.REQUEST:
                with lock:
                    asked = {ps for osd, ps, *_ in requests
                             if osd == self.osd_id} | {bf.ps}
                    requests.append((
                        self.osd_id, bf.ps, bf.state,
                        int(self.ec_perf.get("recover_programs_ready"))
                        >= len(asked)))
            return orig(self, target, op, bf)
        standalone.OSDDaemon._backfill_send = recording
        served_waiting, running = [], []
        try:
            cl.osd_out(victim)
            deadline = time.monotonic() + 120 * _LF
            extra = corpus(35, 8)
            while time.monotonic() < deadline:
                running.append(sum(
                    1 for d in live(c)
                    for bf in list(d._recovering.values())
                    if isinstance(bf, _Backfill) and bf.state == "running"))
                waiting = [(d, ps) for d in live(c)
                           for ps, bf in list(d._recovering.items())
                           if isinstance(bf, _Backfill)
                           and bf.state in ("ready", "reserving")]
                for d, ps in waiting[:1]:
                    name = next((n for n in objs
                                 if cl.osdmap.object_to_pg(1, n)[1] == ps),
                                None)
                    new = next((n for n in extra
                                if cl.osdmap.object_to_pg(1, n)[1] == ps
                                and n not in objs), None)
                    if name is None or new is None:
                        continue
                    ok = cl.read(name) == objs[name]
                    cl.write({new: extra[new]})
                    objs[new] = extra[new]
                    served_waiting.append((ps, ok))
                if not any(d._recovering for d in live(c)) \
                        and ec_sum(c, "recovered_objects") > 0:
                    break
                time.sleep(0.01)
            c.wait_for_clean(timeout=120 * _LF)
        finally:
            standalone.OSDDaemon._backfill_send = orig
        yield {"c": c, "cl": cl, "objs": objs, "victim": victim,
               "old": old, "new": acting_of(c), "degraded": degraded,
               "requests": requests, "served_waiting": served_waiting,
               "running": running}
    finally:
        c.shutdown()


def test_a_pg_that_is_only_degraded_builds_nothing(backfilled):
    """Down and in: nothing is planned, so no program is built, nothing
    is launched, and the pool serves."""
    assert backfilled["degraded"] == {"ready": 0, "pending": 0,
                                      "launches": 0, "recovering": 0,
                                      "read": True}


def test_a_pg_asks_for_its_reservation_only_with_its_program_ready(
        backfilled):
    c = backfilled["c"]
    lost_pgs = {ps for ps, a in backfilled["old"].items()
                if backfilled["victim"] in a}
    asked = {ps for _osd, ps, _state, _ready in backfilled["requests"]}
    assert asked == lost_pgs
    for osd, ps, state, ready in backfilled["requests"]:
        assert state == "reserving", (osd, ps, state)
        assert ready, (osd, ps)
    assert ec_sum(c, "recover_programs_ready") >= len(lost_pgs)
    assert ec_sum(c, "recover_programs_pending") == 0


def test_no_osd_ever_held_more_pgs_than_osd_max_backfills(backfilled):
    c = backfilled["c"]
    peaks = {d.osd_id: int(d.perf.get("backfills_active_max"))
             for d in live(c)}
    assert max(peaks.values()) == 1, peaks
    granted = sum(int(d.perf.get("backfill_reservations_granted"))
                  for d in live(c))
    assert granted >= len({ps for _o, ps, *_ in backfilled["requests"]})
    for d in live(c):
        assert not d._bf_held and not d._bf_queued and not d._recovering


def test_pgs_that_share_an_osd_backfill_one_at_a_time(backfilled):
    """A plan reserves every OSD it moves bytes to or from; on 7 OSDs
    every two PGs of 6 shards share some, so the pool backfills one PG
    at a time, whoever the primaries are, and comes clean (no two PGs
    ever wait for each other: every primary asks in ascending id)."""
    primaries = {a[0] for a in backfilled["old"].values()
                 if backfilled["victim"] in a}
    assert len(primaries) >= 2
    assert max(backfilled["running"]) == 1
    # each PG asked every other OSD of its acting set, once or (a frame
    # lost, asked again) more
    c = backfilled["c"]
    granted = sum(int(d.perf.get("backfill_reservations_granted"))
                  for d in live(c))
    lost_pgs = [ps for ps, a in backfilled["old"].items()
                if backfilled["victim"] in a]
    assert granted >= len(lost_pgs) * K


def test_no_grant_staged_more_than_max_active_times_max_chunk(backfilled):
    """2 objects of 64 KiB: the power of two under 192 KiB."""
    c = backfilled["c"]
    largest = max(int(d.ec_perf.get("recover_grant_bytes_max"))
                  for d in live(c))
    assert 0 < largest <= MAX_ACTIVE * MAX_CHUNK
    assert largest == 2 * K * ROW
    rebuilt = ec_sum(c, "recovered_objects")
    grants = sum(int(d.perf.get("recovery_grants")) for d in live(c))
    cl = backfilled["cl"]
    lost_pgs = {ps for ps, a in backfilled["old"].items()
                if backfilled["victim"] in a}
    assert rebuilt >= sum(
        1 for name in corpus(34)
        if cl.osdmap.object_to_pg(1, name)[1] in lost_pgs)
    assert ec_sum(c, "recover_launches") >= rebuilt // 2
    assert grants >= ec_sum(c, "recover_launches")


def test_a_pg_waiting_for_its_reservation_serves_reads_and_writes(
        backfilled):
    served = backfilled["served_waiting"]
    assert served, "no PG was seen waiting for a reservation"
    assert all(ok for _ps, ok in served)


def test_every_object_reads_back_and_sits_on_the_new_acting_set(backfilled):
    c, cl = backfilled["c"], backfilled["cl"]
    for name, want in backfilled["objs"].items():
        assert cl.read(name) == want, name
        ps = cl.osdmap.object_to_pg(1, name)[1]
        for shard, osd in enumerate(backfilled["new"][ps]):
            assert c.osds[osd].store.exists(shard_cid(f"1.{ps}", shard),
                                            name), (name, shard, osd)
    assert backfilled["victim"] not in {
        o for a in backfilled["new"].values() for o in a}


# -- stale skips across many small grants -----------------------------------

@pytest.mark.parametrize("budget,per", [(2 * K * 1024, 2), (K * 1024, 1)])
def test_a_write_between_stage_and_writeback_is_skipped_grant_after_grant(
        budget, per):
    """The runner's batches are `budget` bytes of helper rows; a client
    write that lands on an object after its batch was staged and before
    it is written back keeps its newer bytes, in every one of the many
    grants."""
    cluster = ShardSet()
    be = ECBackend(f"k={K} m={M}", "1.0", list(range(K + M)), cluster,
                   chunk_size=256)
    rng = np.random.default_rng(7)
    objs = {f"ss-{i}": rng.integers(0, 256, K * 1024, np.uint8)
            for i in range(8)}
    be.write_objects(objs)
    cluster.stores.pop(1)
    plan = be.plan_recovery([1], replacement_osds={1: 90})
    runner = RecoveryRunner([plan], batch=64, push_window_ops=3,
                            push_window_bytes=budget)
    assert [len(b[3]) for b in runner._batches] == [per] * (8 // per)
    newer = {}
    orig = runner._complete

    def overwrite_first_then_complete(entry):
        name = entry[2][0][1]           # the batch's first staged object
        newer[name] = rng.integers(0, 256, K * 1024, np.uint8)
        be.write_objects({name: newer[name]})
        return orig(entry)
    runner._complete = overwrite_first_then_complete
    grants = 0
    while runner.step():
        grants += 1
    runner.finish()
    assert grants >= 8 // per
    assert runner.stats["skipped_stale"] == 8 // per == len(newer)
    assert int(be.perf.get("recover_grant_bytes_max")) == per * K * 1024
    for name, want in objs.items():
        np.testing.assert_array_equal(
            be.read_object(name), newer.get(name, want), err_msg=name)
    assert not plan.remaining
