"""The `rados_recovering` driver at `tiny.py`'s size on the CPU: k=2 m=1,
8 KiB objects, four OSDs of which one is stopped, marked down and then
out in set-up while four writer loops run. A sound run comes out
correct; the controls put in the program's stead (a wrong rebuilt row on
the new member, an acknowledged write missing, two PGs on a target at
once, a grant over the budget, a recovery that ended before the close)
and a fault underneath (a writeback that bends a byte) come out not
correct; a program without the reservation, or whose map keeps a hole,
is refused before the fill; the six `recovery.*` readers read a recorded
table, and nothing where there is nothing."""

import copy

import numpy as np
import pytest

from bench import run as harness
from recovering_controls import recovering_controls
from tiny import (CPU_DEVICE, MANIFEST, PEAKS, SEED, cell_files, failed,
                  json_line, tiny)

NAME = "rados_write_recovering_4m_t16"
# the tiny map, as CRUSH lays 4 PGs on 4 OSDs: osd.1 is no PG's primary
# and holds data slot 1 of PGs 0 and 2, which osd.0 takes when it goes out
LOST = [{"slot": 1, "old": 1, "new": 0, "lost": True}]
TINY = {"victim": 1,
        "repointed_by_pg": {"0": LOST, "1": [], "2": LOST, "3": []}}
# a pace a window of a second and a half can see: a grant of 24 KiB is 2
# objects of 8 KiB, and a nap between grants; stated, as the file's are
PACE = {"osd_recovery_max_chunk": 8192, "osd_recovery_sleep": 0.15}


def tiny_cell():
    cell, workload, config, driver = tiny(NAME)
    config["failure"].update(copy.deepcopy(TINY))
    config["recovery"].update(PACE)
    workload = dict(workload, backlog_objects=96, degraded_lead_s=0.3,
                    recovery_lead_s=0.3, backlog_objects_by_pg={
                        "0": 26, "1": 20, "2": 24, "3": 26})
    return cell, workload, config, driver


def paced(driver, monkeypatch):
    """The tiny file's stated settings, committed as an operator would."""
    down = driver._mark_down

    def slowed(state, log):
        for key, value in PACE.items():
            state["client"].config_set(key, value)
        down(state, log)
    monkeypatch.setattr(driver, "_mark_down", slowed)


@pytest.fixture
def device_path(monkeypatch):
    """The fused device programs, as on the chip: no native host codec."""
    from ceph_tpu.osd import ecbackend
    monkeypatch.setattr(ecbackend, "_host_crc_available", lambda: False)


def run_tiny(monkeypatch, config=None, seconds=1.5):
    cell, workload, tiny_config, driver = tiny_cell()
    paced(driver, monkeypatch)
    return harness.run_cell(MANIFEST, cell, workload, config or tiny_config,
                            driver, CPU_DEVICE, PEAKS, SEED, seconds,
                            trace=False)


@pytest.fixture(scope="module")
def observed():
    """One tiny run's `observe`, for the controls to be built from."""
    from ceph_tpu.osd import ecbackend
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        _, workload, config, driver = tiny_cell()
        paced(driver, patch)
        state = driver.setup(config, workload, SEED, print)
        try:
            driver.warm(state, print)
            run = driver.window(state, 1.5, lambda: None, print)
            driver.finish(state, run, print)
            ob = driver.observe(state, run)
        finally:
            driver.close(state, print)
    return workload, config, driver, ob


def test_sound_run_is_correct(device_path, monkeypatch):
    result, checks = run_tiny(monkeypatch)
    assert failed(checks) == set()
    line = json_line(result)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"client_mb_s", "op_p95_ms", "setup_s"}
    assert list(line)[-1] == "compared"
    compared, notes = line["compared"], line["notes"]
    assert notes["boots"] == 1 and notes["failure"]["victim"] == 1
    assert notes["failure"]["lost_slot_by_pg"] == {"0": 1, "1": None,
                                                   "2": 1, "3": None}
    # every window write compared, every backlog object of a PG that lost
    # a slot held to the plain decode, on the new set
    assert compared["window_objects_compared"]["value"] >= 1
    assert compared["window_writes_missing"]["value"] == 0
    assert compared["rebuilt_rows_compared"]["value"] == 50
    # rebuilt on the device a PG at a time, in 2-object grants, through
    # the window and past it
    assert compared["recover_launches"]["value"] >= 25
    assert compared["recover_host_launches"]["value"] == 0
    assert compared["backfills_active_max"]["value"] == 1
    assert compared["grant_bytes_max"]["value"] == 2 * 8192 \
        <= compared["grant_bytes_max"]["limit"] == 3 * 8192
    assert compared["programs_pending_at_open"]["value"] == 0
    assert compared["slices_without_recovery"]["value"] == 0
    assert compared["backlog_left_at_close"]["value"] >= 1
    assert compared["pool_clean"]["value"] == 1
    # seen, not limited
    assert notes["time_to_clean_s"] is not None
    assert sum(notes["rebuilt_by_slice"]) == notes["rebuilt_in_window"] >= 2
    assert notes["rebuilt_since_failure"] >= 50
    opened = notes["window_opened"]
    assert opened["programs_pending"] == 0 and opened["grants"] >= 1
    assert opened["s_after_out"] >= opened["ready_s_after_out"] + 0.3


def test_the_files_constants_are_held_to_the_map_of_the_run(device_path,
                                                           monkeypatch):
    """The file as committed states the 12-OSD map and the 8 MiB chunk;
    on the tiny map under the tiny pace it is another pool, and the
    constants say so."""
    _, _, config, _ = tiny(NAME)
    result, checks = run_tiny(monkeypatch, config=config)
    assert {"victim_off_file", "repointed_pgs_off_file",
            "settings_off_file"} <= failed(checks)
    assert result["correct"] is False


def test_work_bytes_from_shapes():
    _, workload, config, driver = cell_files(NAME)
    # a client write as the write cell's; a rebuilt object 8 helper rows
    # of 512 KiB into the decode, the row its PG lost and its crc word out
    assert driver.work_bytes(config, workload, 3) == 3 * 5_772_800
    assert driver.recovery_work_bytes(config, 1) == 9 * 524_288 + 4
    assert driver.recovery_work_bytes(config, 0) == 0
    # a grant: the power of two under 24 MiB over 4 MiB an object
    assert driver.grant_objects(config) == 4


def test_the_committed_files_state_the_deployment():
    from ceph_tpu.utils.config import OPTIONS
    _, workload, config, _ = cell_files(NAME)
    write = cell_files("rados_write_4m_t16")
    for key in ("profile", "geometry", "cluster", "store_as_found"):
        assert config[key] == write[2][key], key
    # the traffic is the write cell's, letter for letter
    for key in ("op", "loops", "distinct_payloads", "warm_min_s",
                "warm_quiet_s", "warm_max_s", "readback_objects",
                "trace_after_s", "trace_seconds", "loop"):
        assert workload[key] == write[1][key], key
    # the stated settings are the program's defaults: stated, not set
    defaults = {o.name: o.default for o in OPTIONS}
    for key, value in config["recovery"].items():
        if key != "note":
            assert defaults[key] == value, key
    assert config["recovery"]["osd_max_backfills"] == 1
    failure = config["failure"]
    n = workload["backlog_objects"]
    assert 1024 <= n <= 2048 and n & (n - 1) == 0
    assert sum(workload["backlog_objects_by_pg"].values()) == n
    assert failure["pgs_with_a_hole"] == 0
    assert len(failure["repointed_by_pg"]) == config["cluster"]["pg_num"]
    for pg, moves in failure["repointed_by_pg"].items():
        assert sum(r["lost"] for r in moves) == 1
        assert all(r["lost"] == (r["old"] == failure["victim"])
                   for r in moves)
        assert failure["backfill_targets_by_pg"][pg] == next(
            r["new"] for r in moves if r["lost"])
    assert sorted(pg for pgs in failure["primaries"].values()
                  for pg in pgs) == list(range(8))
    assert len(config["guarantees"]) == 7


def test_a_program_without_the_reservation_is_refused_at_once(monkeypatch):
    from ceph_tpu.utils import config as program_config
    monkeypatch.setattr(
        program_config, "OPTIONS",
        [o for o in program_config.OPTIONS if o.name != "osd_max_backfills"])
    _, workload, config, driver = tiny_cell()
    with pytest.raises(SystemExit, match="osd_max_backfills"):
        driver.setup(config, workload, SEED, print)


def test_a_program_without_the_counters_is_refused_at_once(monkeypatch):
    from ceph_tpu.osd import ecbackend
    real = ecbackend.ec_perf_counters

    class Without:
        def dump(self):
            out = real().dump()
            del out["recover_grant_bytes_max"]
            return out
    monkeypatch.setattr(ecbackend, "ec_perf_counters", Without)
    _, workload, config, driver = tiny_cell()
    with pytest.raises(SystemExit, match="recover_grant_bytes_max"):
        driver.setup(config, workload, SEED, print)


def test_a_map_that_keeps_a_hole_is_refused_before_the_fill(monkeypatch):
    cell, workload, config, driver = tiny_cell()
    monkeypatch.setattr(driver.recovered_pool, "holes",
                        lambda acting, n_osds: [1])
    lines = []
    with pytest.raises(SystemExit, match="a hole"):
        harness.run_cell(MANIFEST, cell, workload, config, driver,
                         CPU_DEVICE, PEAKS, SEED, 1.0, trace=False)
    state = driver.setup(config, workload, SEED, lines.append)
    with pytest.raises(SystemExit, match="a hole"):
        driver.warm(state, lines.append)
    assert state["cluster"] is None              # stopped on the way out
    assert not any("working set" in line for line in lines)


# -- controls: in the program's stead, one guarantee broken -------------

def test_the_sound_observation_and_the_sound_reference_pass(observed):
    workload, config, driver, ob = observed
    assert failed(driver.compare(config, workload, ob)) == set()
    controls = recovering_controls(config, driver, ob)
    assert failed(driver.compare(config, workload,
                                 controls["_sound_reference"])) == set()


@pytest.mark.parametrize("control,readings", [
    ("wrong_rebuilt_row_on_the_new_member", {"stored_rows_wrong",
                                             "stored_crcs_wrong",
                                             "rebuilt_rows_wrong",
                                             "rebuilt_crcs_wrong"}),
    ("acknowledged_write_missing", {"window_writes_missing"}),
    ("two_pgs_on_a_target_at_once", {"backfills_active_max"}),
    ("grant_over_the_budget", {"grant_bytes_max"}),
    ("recovery_ended_before_the_close", {"slices_without_recovery",
                                         "backlog_left_at_close"}),
])
def test_a_broken_guarantee_is_not_correct(observed, control, readings):
    workload, config, driver, ob = observed
    broken = recovering_controls(config, driver, ob)[control]
    assert failed(driver.compare(config, workload, broken)) == readings


# -- a fault: the rebuild broken underneath -------------------------------

def test_a_writeback_that_bends_a_byte_is_not_correct(device_path,
                                                      monkeypatch):
    from ceph_tpu.osd.ecbackend import ECBackend
    real = ECBackend._writeback_rebuilt

    def altered(self, lost, subgroup, rebuilt_all, crcs, sl, counters,
                window=None):
        bent = np.array(rebuilt_all)
        bent[0, 0, -1] ^= 1
        return real(self, lost, subgroup, bent, crcs, sl, counters,
                    window=window)
    monkeypatch.setattr(ECBackend, "_writeback_rebuilt", altered)
    result, checks = run_tiny(monkeypatch)
    assert "stored_rows_wrong" in failed(checks)
    assert result["correct"] is False


# -- the readers ----------------------------------------------------------

READERS = ("recovery.objects_per_s", "recovery.grant_ms",
           "recovery.host_ms_per_obj", "recovery.device_wait_ms_per_obj",
           "recovery.reserve_wait_ms", "recovery.programs_roofline_pct")


def _run(**more):
    return dict({"ops": [], "t0": 100.0, "t1": 130.0, "window_s": 30.0,
                 "counters": {}, "trace": None, "set_up_seconds": 1.0,
                 "peaks": {"hbm_bytes_per_s": 819e9}}, **more)


def _record(name, dur, self_s, nbytes=None):
    return {"name": name, "start": 105.0, "dur": dur, "self": self_s,
            "trace_id": None, "nbytes": nbytes}


def test_the_readers_on_a_recorded_table(monkeypatch):
    from ceph_tpu.utils import tracing
    _, workload, config, driver = cell_files(NAME)
    row = 524_288
    # three grants: two launches of 4 objects and one of 2: 10 objects
    table = [
        _record("recovery.grant", 0.200, 0.010, 4 * 8 * row),
        _record("recovery.grant", 0.300, 0.020, 4 * 8 * row),
        _record("recovery.grant", 0.100, 0.000, 2 * 8 * row),
        _record("recovery.pull", 0.200, 0.200, 4 * 8 * row),
        _record("recovery.pull", 0.120, 0.120, 4 * 8 * row),
        _record("recovery.stage", 0.004, 0.004),
        _record("recovery.launch", 0.030, 0.030, 4 * 8 * row),
        _record("recovery.launch", 0.030, 0.030, 4 * 8 * row),
        _record("recovery.launch", 0.020, 0.020, 2 * 8 * row),
        _record("recovery.fetch", 0.002, 0.002),
        _record("recovery.push", 0.100, 0.080),
        _record("recovery.settle", 0.010, 0.006),
        _record("recovery.reserve.wait", 9.0, 9.0),
        _record("osd.op", 0.100, 0.010)]
    monkeypatch.setattr(tracing, "span_log",
                        lambda since=None, until=None: list(table))
    run = _run(
        counters={"recovered_objects": 540},
        trace={"busy_s": 0.004, "window_s": 5.0},
        traced_ops=70, traced_work_bytes=driver.work_bytes(config, workload,
                                                           70),
        recovery={"helper_bytes_an_object": 8 * row,
                  "work_bytes_an_object":
                      driver.recovery_work_bytes(config, 1),
                  "rebuilt_in_window": 540, "window_s": 30.0},
        gauges_at_end={"reserve_wait_s": 36.0, "reserve_waits": 8})
    read = {name: harness.load_module("layer_metrics", name).compute(run)
            for name in READERS}
    assert read["recovery.objects_per_s"] == 18.0
    assert read["recovery.grant_ms"] == pytest.approx(200.0)
    assert read["recovery.host_ms_per_obj"] == pytest.approx(
        (0.030 + 0.320 + 0.004 + 0.080 + 0.080 + 0.006) / 10 * 1e3)
    assert read["recovery.device_wait_ms_per_obj"] == pytest.approx(0.2)
    assert read["recovery.reserve_wait_ms"] == pytest.approx(4500.0)
    least = (70 * 5_772_800 + 10 * (9 * row + 4)) / 819e9
    assert read["recovery.programs_roofline_pct"] == pytest.approx(
        100 * least / 0.004)
    assert read["recovery.programs_roofline_pct"] < 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_reads_nothing(name, monkeypatch):
    """An untraced run, a program whose launches carry no bytes (the
    parent's), and a run without the driver's `recovery` block."""
    from ceph_tpu.utils import tracing
    compute = harness.load_module("layer_metrics", name).compute
    assert compute(_run()) is None
    monkeypatch.setattr(
        tracing, "span_log", lambda since=None, until=None: [
            _record("ecbackend.recover.launch", 0.03, 0.03),
            _record("osd.op", 0.1, 0.01)])
    traced = _run(trace={"busy_s": 0.004, "window_s": 5.0}, traced_ops=3,
                  traced_work_bytes=1.0)
    assert compute(traced) is None
    assert compute(dict(traced, recovery={}, gauges_at_end={})) is None
