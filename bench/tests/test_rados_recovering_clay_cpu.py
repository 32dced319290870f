"""The `rados_recovering_clay` driver on the CPU at a small size: the
cell's own map (13 OSDs, 8 PGs, osd.3 out) and Clay k=8 m=4 d=11, with
objects of 64 KiB (Clay's smallest stripe) and a backlog of 128, paced
so that a window of a second and a half sees a steady backfill. A sound
run comes out correct, guarantee (h) exact; the controls (a wrong byte
in a rebuilt row, an acknowledged write missing, an object rebuilt from
whole rows, a recovery that ended before the close) come out not
correct; the two new readers read a recorded run, and nothing where
there is nothing."""

import copy

import pytest

from bench import run as harness
from clay_recovering_controls import clay_recovering_controls
from tiny import CPU_DEVICE, MANIFEST, PEAKS, SEED, cell_files, failed

NAME = "rados_write_recovering_clay_4m_t16"
# a grant of 48 KiB of helper bytes is 2 objects of 11 x 2 KiB
PACE = {"osd_recovery_max_chunk": 16384, "osd_recovery_sleep": 0.15}
BACKLOG_BY_PG = {"0": 15, "1": 13, "2": 18, "3": 14, "4": 14, "5": 20,
                 "6": 15, "7": 19}


def small_cell():
    cell, workload, config, driver = cell_files(NAME)
    config = copy.deepcopy(config)
    config["geometry"].update(object_bytes=65536, shard_row_bytes=8192)
    config["recovery"].update(PACE)
    workload = dict(workload, loops=4, distinct_payloads=8, warm_min_s=0.5,
                    warm_quiet_s=0.3, readback_objects=4,
                    backlog_objects=128, backlog_objects_by_pg=BACKLOG_BY_PG,
                    degraded_lead_s=0.3, recovery_lead_s=0.3)
    return cell, workload, config, driver


@pytest.fixture(scope="module")
def observed():
    """One sound run's `observe` and the window's `run`."""
    from ceph_tpu.osd import ecbackend
    _, workload, config, driver = small_cell()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        down = driver.rados_recovering._mark_down

        def paced(state, log):
            for key, value in PACE.items():
                state["client"].config_set(key, value)
            down(state, log)
        patch.setattr(driver.rados_recovering, "_mark_down", paced)
        state = driver.setup(config, workload, SEED, print)
        try:
            driver.warm(state, print)
            run = driver.window(state, 1.5, lambda: None, print)
            driver.finish(state, run, print)
            ob = driver.observe(state, run)
        finally:
            driver.close(state, print)
    return workload, config, driver, ob, run


def test_the_sound_run_is_correct(observed):
    workload, config, driver, ob, run = observed
    checks = {c["name"]: c for c in driver.compare(config, workload, ob)}
    assert {name for name, c in checks.items() if not c["ok"]} == set()
    assert checks["wire_bytes_off_the_range_plan"]["value"] == 0
    assert checks["rebuilt_rows_compared"]["value"] == 128
    assert checks["window_objects_compared"]["value"] >= 1
    rec = run["recovery"]
    assert rec["helper_bytes_an_object"] == 11 * 2048
    assert rec["range_bytes_served_in_window"] > 0


@pytest.mark.parametrize("control,readings", [
    ("wrong_rebuilt_row_on_the_new_member", {"stored_rows_wrong",
                                             "stored_crcs_wrong",
                                             "rebuilt_rows_wrong",
                                             "rebuilt_crcs_wrong"}),
    ("acknowledged_write_missing", {"window_writes_missing"}),
    ("rebuilt_from_whole_rows", {"wire_bytes_off_the_range_plan"}),
    ("recovery_ended_before_the_close", {"slices_without_recovery",
                                         "backlog_left_at_close"}),
])
def test_a_broken_guarantee_is_not_correct(observed, control, readings):
    workload, config, driver, ob, _ = observed
    broken = clay_recovering_controls(config, ob)[control]
    assert failed(driver.compare(config, workload, broken)) == readings


def test_set_up_refuses_a_clay_write_of_two_launches(monkeypatch):
    """A program without the vector code's fused write (`encode_chunks`,
    then a crc launch) is refused in set-up, before the boot."""
    from ceph_tpu.ec.clay import Clay
    _, _, config, driver = small_cell()
    monkeypatch.setattr(Clay, "vector_encode_matrix", lambda self: None)
    with pytest.raises(SystemExit, match="1 encode and 0 fused launches"):
        driver._build_write_programs(config, print)


def test_shapes_of_the_cells_files():
    _, workload, config, driver = cell_files(NAME)
    # a write: 12 rows of 512 KiB and their 12 x 128 crc words
    assert driver.work_bytes(config, workload, 1) == 6_297_600
    # a rebuilt object: 11 quarter rows in, the row and its crc word out
    assert driver.helper_bytes_an_object(config) == 1_441_792
    assert driver.recovery_work_bytes(config, 1) == 1_966_084
    assert driver.grant_objects(config) == 16
    assert sum(workload["backlog_objects_by_pg"].values()) \
        == workload["backlog_objects"]
    rs = cell_files("rados_write_recovering_4m_t16")[1]
    for key in ("op", "loops", "distinct_payloads", "warm_min_s",
                "warm_quiet_s", "warm_max_s", "readback_objects",
                "trace_after_s", "trace_seconds", "loop",
                "degraded_lead_s", "recovery_lead_s"):
        assert workload[key] == rs[key], key


def _run(**more):
    return dict({"ops": [], "t0": 100.0, "t1": 130.0, "window_s": 30.0,
                 "counters": {}, "trace": None}, **more)


def test_the_readers_on_a_recorded_run(monkeypatch):
    from ceph_tpu.utils import tracing
    per = 1_441_792

    def rec(name, dur, nbytes=None):
        return {"name": name, "start": 105.0, "dur": dur, "self": dur,
                "trace_id": None, "nbytes": nbytes}
    table = [rec("recovery.launch", 0.01, 16 * per),
             rec("recovery.launch", 0.01, 4 * per),
             rec("recovery.serve_ranges", 0.030, 16 * per // 11),
             rec("recovery.serve_ranges", 0.050, 16 * per // 11),
             rec("recovery.serve_ranges.verify", 0.020)]
    monkeypatch.setattr(tracing, "span_log",
                        lambda since=None, until=None: list(table))
    run = _run(trace={"busy_s": 0.1, "window_s": 5.0},
               recovery={"helper_bytes_an_object": per,
                         "rebuilt_in_window": 300, "window_s": 30.0,
                         "range_bytes_served_in_window": 300 * per + 5})
    serve = harness.load_module("layer_metrics", "recovery.serve_ms_per_obj")
    wire = harness.load_module("layer_metrics", "recovery.wire_bytes_per_obj")
    assert serve.compute(run) == pytest.approx(80.0 / 20)
    assert wire.compute(run) == pytest.approx(per + 5 / 300)


@pytest.mark.parametrize("name", ["recovery.serve_ms_per_obj",
                                  "recovery.wire_bytes_per_obj"])
def test_a_reader_with_nothing_to_read_reads_nothing(name, monkeypatch):
    """An untraced run, and a program without the span or the counter
    (the parent's): the driver leaves no range bytes on the run."""
    from ceph_tpu.utils import tracing
    compute = harness.load_module("layer_metrics", name).compute
    assert compute(_run()) is None
    monkeypatch.setattr(tracing, "span_log", lambda since=None, until=None: [
        {"name": "recovery.launch", "start": 105.0, "dur": 0.01,
         "self": 0.01, "nbytes": 16 * 1_441_792}])
    assert compute(_run(trace={"busy_s": 0.1}, recovery={
        "helper_bytes_an_object": 1_441_792, "rebuilt_in_window": 10,
        "window_s": 30.0})) is None
