"""Thrasher fault matrix — the wire tier under a seeded compound-
fault schedule (the teuthology thrash suite role, ref: qa/tasks/
ceph_manager.py), with cephx + secure frames ON and both store
backends.

Layout:
  * tier-1 smoke: 2 seeds (one per store) run in every `-m 'not
    slow'` pass — chaos coverage never silently rots;
  * the full matrix: >=10 seeds x {mem, tin}, selected with
    `-m chaos` (marked slow so the tier-1 budget is untouched).

Every cell checks the four invariants (convergence, exactly-once
bytes, no resurrection, fsck-clean stores) after each round's heal.
A failing cell prints its seed and the one-command reproducer
(`python tools/thrash.py --seed N --store S ...`) via
InvariantViolation's message.
"""

import pytest

from ceph_tpu.chaos import Thrasher

# the matrix axes: seeds are arbitrary but FIXED — a failure report
# names (seed, store) and tools/thrash.py replays it bit-for-bit
MATRIX_SEEDS = [11, 23, 37, 41, 59, 67, 73, 89, 97, 101]
# the tin cell + the sharded smoke stay tier-1 (store-backed + r13
# dispatch); the plain mem seed repeats their schedule shape at ~14 s
# and moved to the nightly (r20 CI-budget trim)
SMOKE = [pytest.param(11, "mem", marks=pytest.mark.slow),
         (23, "tin")]


def run_cell(seed: int, store: str, tmp_path) -> dict:
    th = Thrasher(seed, store=store, rounds=2, ops=6,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()   # raises InvariantViolation (seed + repro
    #                     in the message) on any violated invariant
    assert report["objects_verified"] > 0, report
    return report


@pytest.mark.chaos
@pytest.mark.parametrize("seed,store", SMOKE)
def test_thrash_smoke(seed, store, tmp_path):
    """The tier-1 subset: one seed per store backend."""
    run_cell(seed, store, tmp_path)


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("store", ["mem", "tin"])
@pytest.mark.parametrize("seed", MATRIX_SEEDS)
def test_thrash_matrix(seed, store, tmp_path):
    """The full >=10-seed x {MemStore, TinStore} matrix (`-m chaos`)."""
    if (seed, store) in SMOKE:
        pytest.skip("covered by the tier-1 smoke cell")
    run_cell(seed, store, tmp_path)


@pytest.mark.chaos
@pytest.mark.parametrize("seed,store", [(43, "mem")])
def test_thrash_sharded_smoke(seed, store, tmp_path):
    """r13 tier-1 cell: `osd_op_num_shards = 2` + the reactor
    messenger under the full fault schedule (kills land mid-window
    via socket injection) — exactly-once and no-resurrection must
    hold when ops hash across per-shard mClock queues."""
    th = Thrasher(seed, store=store, rounds=2, ops=6, op_shards=2,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed,store", [(53, "tin"), (61, "mem")])
def test_thrash_sharded_matrix(seed, store, tmp_path):
    """Deeper sharded-dispatch cells (`-m chaos`): 4 shards, both
    stores — beyond the tier-1 2-shard representative."""
    th = Thrasher(seed, store=store, rounds=2, ops=6, op_shards=4,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed,store", [(47, "tin")])
def test_thrash_overwrite_during_faults(seed, store, tmp_path):
    """r16 cell (`-m chaos`): seed-deterministic partial overwrites
    (write_at) land WITH the round's faults still live, so SIGKILLs
    catch RMWs mid-flight — the stripe journal's remount replay must
    keep every acked overwrite exactly-once (last acked bytes,
    byte-exact), removed objects removed, and the TinStore
    directories fsck-clean after the final crash-shutdown. The
    tier-1 representative of the journal's crash contract is the
    hermetic SIGKILL-at-every-phase-boundary matrix in
    tests/test_rmw_delta.py (TinStore remount + fsck included) —
    this cell adds the live-wire concurrency on the chaos tier,
    where the 870 s tier-1 budget has no headroom left."""
    th = Thrasher(seed, store=store, rounds=1, ops=6,
                  overwrite_during_faults=True,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["rmw_overwrite_checks"] > 0, report
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed,store", [(67, "mem"), (79, "tin")])
def test_thrash_overwrite_matrix(seed, store, tmp_path):
    """Deeper overwrite-during-faults cells (`-m chaos`): more rounds,
    both stores, beyond the tier-1 tin representative."""
    th = Thrasher(seed, store=store, rounds=3, ops=6,
                  overwrite_during_faults=True,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["rmw_overwrite_checks"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed,store", [(19, "mem"), (31, "tin")])
def test_thrash_degraded_reads_never_block(seed, store, tmp_path):
    """Round-11 invariant cell: with each round's faults still LIVE
    (dead primaries un-revived, mon churn un-healed, injection on),
    every acked object must read back bit-exact through the
    degraded-read fast path — no read ever blocks on
    wait_for_clean."""
    th = Thrasher(seed, store=store, rounds=2, ops=6,
                  read_during_faults=True,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["degraded_read_checks"] > 0, report
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", [311])
def test_thrash_transient_smoke(seed, tmp_path):
    """r17 cell (slow since r20: 7-9s on a quiet box but >120s with
    repeated in-suite load flakes when heartbeat stretching pushes the
    policy mid-override — the r18/r19-noted flake; tier-1 keeps the
    transient plane through test_repair_policy's deterministic
    virtual-clock cells, which don't ride real heartbeats): the
    transient-vs-real failure mix — a seeded
    kill stream whose victims auto-revive inside/outside the
    osd_repair_delay window (k=2 m=3 so single losses keep >= 2 spare
    redundancy and really defer). The run itself asserts the two
    policy invariants after every heal: (a) an inside-window revive
    over a quiet window moves ZERO repair bytes (the cursor re-check
    cancel), (b) no at-m-1 stripe is ever parked and the rebuild
    queue ships no risk inversions. This seed's draws include a quiet
    probe, so the zero-byte check provably fired."""
    th = Thrasher(seed, store="mem", rounds=1, ops=4,
                  transient_fraction=0.9, n_osds=7,
                  profile="plugin=tpu_rs k=2 m=3")
    report = th.run()
    assert report["transient_kills"] > 0, report
    # the zero-byte claim fired — or was provably skipped because the
    # policy was mid-override (a loaded box stretching heartbeats into
    # spurious down-marks; the skip is logged, never silent)
    assert report["transient_noop_checks"] \
        + report["transient_noop_skips"] > 0, report
    assert report["repair_deferred_stripes"] > 0, report
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed,store,fraction", [(313, "mem", 0.9),
                                                 (317, "tin", 0.6)])
def test_thrash_transient_matrix(seed, store, fraction, tmp_path):
    """Deeper transient-mix cells (`-m chaos`): more rounds, a lower
    transient fraction (real + transient kills interleave), and the
    TinStore remount path under the auto-revive stream. Same policy
    invariants as the smoke, checked after every heal."""
    th = Thrasher(seed, store=store, rounds=2, ops=5,
                  transient_fraction=fraction, n_osds=7,
                  profile="plugin=tpu_rs k=2 m=3",
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["transient_kills"] > 0, report
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.parametrize("seed,store", [(3, "mem")])
def test_thrash_disk_full_smoke(seed, store, tmp_path):
    """r21 tier-1 cell: the seeded disk_full fault stream — live
    capacity shrinks drive the ladder to FULL mid-write-window
    (writes park RADOS-style, reads keep serving, the window heals by
    restoring capacity and every parked write drains exactly-once)
    plus one-shot ENOSPC injection at seeded store txn phases. The
    heal asserts zero surfaced client write errors and fsck-clean
    stores on top of the four standing invariants."""
    th = Thrasher(seed, store=store, rounds=1, ops=6, disk_full=True)
    report = th.run()
    assert report["full_windows"] > 0, report
    assert report["full_reads_served"] > 0, report
    assert report["full_parked_drained"] > 0, report
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed,store,rounds", [(5, "tin", 2),
                                               (7, "mem", 2)])
def test_thrash_disk_full_matrix(seed, store, rounds, tmp_path):
    """Deeper disk_full cells (`-m chaos`): more rounds and the
    TinStore path, where the seeded ENOSPC injection lands across the
    WAL/flush/compaction phase set and every directory must come back
    fsck-clean after the round's crash-heal."""
    th = Thrasher(seed, store=store, rounds=rounds, ops=6,
                  disk_full=True,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["full_windows"] > 0, report
    assert report["full_parked_drained"] > 0, report
    assert report["enospc_injected"] > 0, report
    if store == "tin":
        assert report["fsck_clean_stores"] > 0, report


@pytest.mark.chaos
@pytest.mark.parametrize("seed,store", [(3, "mem")])
def test_thrash_link_degrade_smoke(seed, store, tmp_path):
    """r22 tier-1 cell: the seeded link_degrade fault stream — a
    one-way delay injected on one directed link must flip
    OSD_SLOW_PING_TIME naming EXACTLY that link within two grace
    windows, reprice the r14 helper ranking off the degraded peer
    (net_helper_penalties pinned), and clear after the heal — on top
    of the standing integrity invariants."""
    th = Thrasher(seed, store=store, rounds=1, ops=4,
                  link_degrade=True)
    report = th.run()
    assert report["link_windows"] > 0, report
    assert report["link_health_flips"] > 0, report
    assert report["link_repriced"] > 0, report
    assert report["link_health_clears"] > 0, report
    assert report["objects_verified"] > 0, report


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed,store,rounds", [(5, "mem", 2),
                                               (11, "tin", 2)])
def test_thrash_link_degrade_matrix(seed, store, rounds, tmp_path):
    """Deeper link_degrade cells (`-m chaos`): more rounds (a fresh
    seeded victim pair each) and the TinStore path, where the
    degraded link's store sub-ops ride the same injected delay and
    the exact-link naming contract must still hold."""
    th = Thrasher(seed, store=store, rounds=rounds, ops=4,
                  link_degrade=True,
                  store_dir=str(tmp_path / "osds")
                  if store == "tin" else None)
    report = th.run()
    assert report["link_windows"] > 0, report
    assert report["link_health_flips"] == report["link_windows"]
    assert report["link_health_clears"] == report["link_windows"]
    assert report["link_repriced"] == report["link_windows"]


def test_same_seed_same_schedule(tmp_path):
    """Reproducibility contract: two Thrashers with one seed draw the
    IDENTICAL fault schedule (victims, knob values, data sizes) —
    what makes `tools/thrash.py --seed N` a real reproducer. The
    schedules are compared as logged, excluding wall-clock-dependent
    park/heal noise."""

    def schedule_of(th):
        return [line for line in th.schedule
                if not line.startswith("parked")]

    a = Thrasher(42, store="mem", rounds=1, ops=5)
    a.run()
    b = Thrasher(42, store="mem", rounds=1, ops=5)
    b.run()
    assert schedule_of(a) == schedule_of(b)


def test_distinct_seeds_distinct_schedules():
    """Different seeds must actually explore different schedules (a
    constant schedule would make the matrix one test run 20 times)."""
    drawn = set()
    for seed in MATRIX_SEEDS[:4]:
        th = Thrasher(seed)
        menu = th._menu()
        draws = tuple(th.rng.randrange(len(menu)) for _ in range(12))
        drawn.add(draws)
    assert len(drawn) == len(MATRIX_SEEDS[:4])
