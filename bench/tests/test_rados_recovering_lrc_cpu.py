"""The `rados_recovering_lrc` driver on the CPU at a small size: the
cell's own map (17 OSDs, 8 PGs, osd.4 out) and LRC k=8 m=4 l=3, with
objects of 64 KiB and a backlog of 128, paced so that a window of six
seconds sees a steady backfill (17 daemons on the CPU plan, build and
launch slowly). A sound run comes out correct,
guarantee (h) exact; the controls (a wrong byte in a rebuilt row under a
crc taken of it, an acknowledged write missing, an object rebuilt from
more rows than its group, a plan laddered past the group) come out not
correct; the two new readers read a recorded run, and nothing where
there is nothing."""

import copy

import numpy as np
import pytest

from bench import run as harness
from bench.reference import lrc_codeword
from tiny import SEED, cell_files, failed

NAME = "rados_write_recovering_lrc_4m_t16"
# a grant of 48 KiB of helper bytes is 2 objects of 3 x 8 KiB
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
        # 17 daemons on a shared CPU fold the out map slower than the
        # client's default 15 s wait for it
        from ceph_tpu.osd.standalone import Client
        out = Client.osd_out
        patch.setattr(Client, "osd_out",
                      lambda self, osd, timeout=15.0: out(self, osd, 120.0))
        state = driver.setup(config, workload, SEED, print)
        try:
            driver.warm(state, print)
            run = driver.window(state, 6.0, lambda: None, print)
            driver.finish(state, run, print)
            ob = dict(driver.observe(state, run),
                      planned=driver.planned_since_boot(state))
        finally:
            driver.close(state, print)
    return workload, config, driver, ob, run


def test_the_sound_run_is_correct(observed):
    workload, config, driver, ob, run = observed
    checks = {c["name"]: c for c in driver.compare(config, workload, ob)}
    assert {name for name, c in checks.items() if not c["ok"]} == set()
    assert checks["wire_bytes_off_the_local_plan"]["value"] == 0
    assert checks["rebuilt_rows_compared"]["value"] == 128
    assert checks["window_objects_compared"]["value"] >= 1
    assert checks["planner_local_plans"]["value"] >= 8
    assert run["recovery"]["helper_bytes_an_object"] == 3 * 8192


def _bent(config, ob):
    """The first rebuilt backlog object with a wrong byte in the row on
    its new member, its crc taken of the wrong row."""
    lost_slot = ob["map"]["lost_slot"]
    i = next(i for i, o in enumerate(ob["objects"])
             if o["origin"] == "backlog" and lost_slot[o["pg"]] is not None)
    objects = list(ob["objects"])
    o = dict(objects[i], rows=list(objects[i]["rows"]),
             crcs=list(objects[i]["crcs"]))
    slot = lost_slot[o["pg"]]
    row = np.array(o["rows"][slot])
    row[100] ^= 1
    o["rows"][slot] = row
    o["crcs"][slot] = int(lrc_codeword.crcs(row[None, :])[0])
    objects[i] = o
    return dict(ob, objects=objects)


def _controls(config, ob):
    per = 3 * 8192
    since = ob["since_failure"]
    acked = next(i for i, o in enumerate(ob["objects"])
                 if o["origin"] == "window")
    return {
        "wrong_rebuilt_row_on_the_new_member": _bent(config, ob),
        "acknowledged_write_missing": dict(
            ob, objects=ob["objects"][:acked] + ob["objects"][acked + 1:]),
        "rebuilt_from_k_rows": dict(ob, since_failure=dict(
            since, recover_wire_bytes=since["recover_wire_bytes"]
            + since["recovered_objects"] * (8 * 8192 - per))),
        "a_plan_laddered_past_the_group": dict(ob, planned=dict(
            ob["planned"], planner_full_plans=1)),
    }


@pytest.mark.parametrize("control,readings", [
    ("wrong_rebuilt_row_on_the_new_member", {"stored_rows_wrong",
                                             "stored_crcs_wrong",
                                             "rebuilt_rows_wrong",
                                             "rebuilt_crcs_wrong"}),
    ("acknowledged_write_missing", {"window_writes_missing"}),
    ("rebuilt_from_k_rows", {"wire_bytes_off_the_local_plan"}),
    ("a_plan_laddered_past_the_group", {"planner_full_plans"}),
])
def test_a_broken_guarantee_is_not_correct(observed, control, readings):
    workload, config, driver, ob, _ = observed
    broken = _controls(config, ob)[control]
    assert failed(driver.compare(config, workload, broken)) == readings


def test_set_up_refuses_an_lrc_write_of_a_launch_a_layer(monkeypatch):
    """A program without the layers' composed generator (the generic
    `encode_chunks`, a launch a layer, then a crc launch) is refused in
    set-up, before the boot."""
    from ceph_tpu.ec.lrc import Lrc
    _, _, config, driver = small_cell()
    monkeypatch.setattr(Lrc, "encode_matrix", lambda self: None)
    with pytest.raises(SystemExit, match="1 encode and 0 fused launches"):
        driver._build_write_programs(config, print)


def test_the_victim_rule_takes_the_codes_data_slots():
    _, workload, config, driver = cell_files(NAME)
    slots = driver.data_slots(config["profile"])
    assert slots == [2, 3, 6, 7, 10, 11, 14, 15] \
        == config["geometry"]["data_slots"]
    assert lrc_codeword.mapping(8, 4, 3) == config["geometry"]["mapping"]
    # slot 1 is a global parity, slot 2 data: osd.2 holds data in PG 0
    # (two objects), osd.1 in PG 1 (one)
    acting = {0: [9, 1, 2] + list(range(3, 16)),
              1: [9, 2, 1] + list(range(3, 16))}
    assert driver.choose_victim(acting, slots, [0, 0, 1], [1, 2]) == 2


def test_shapes_of_the_cells_files():
    _, workload, config, driver = cell_files(NAME)
    # a write: 16 rows of 512 KiB and their 16 x 128 crc words
    assert driver.work_bytes(config, workload, 1) == 8_396_800
    # a rebuilt object: 3 rows in, the row and its crc word out
    assert driver.helper_bytes_an_object(config) == 1_572_864
    assert driver.recovery_work_bytes(config, 1) == 2_097_156
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
                 "counters": {}, "trace": None, "traced_ops": 40}, **more)


def test_the_readers_on_a_recorded_run(monkeypatch):
    from ceph_tpu.utils import tracing

    def rec(name, dur, tags=None):
        return {"name": name, "start": 105.0, "dur": dur, "self": dur,
                "trace_id": None, "nbytes": None, "tags": tags}
    table = [rec("recovery.launch", 0.01,
                 {"objects": 16, "recover_helper_reads": 48}),
             rec("recovery.launch", 0.01,
                 {"objects": 4, "recover_helper_reads": 12}),
             rec("recovery.grant", 0.2, {"pgs": [1]}),
             rec("ecbackend.write.slots", 0.0004),
             rec("ecbackend.write.slots", 0.0006)]
    monkeypatch.setattr(tracing, "span_log",
                        lambda since=None, until=None: list(table))
    run = _run(trace={"busy_s": 0.1, "window_s": 5.0})
    helpers = harness.load_module("layer_metrics", "recovery.helpers_per_obj")
    slots = harness.load_module("layer_metrics", "ec.slot_order_ms_per_op")
    assert helpers.compute(run) == 3.0
    assert slots.compute(run) == pytest.approx(1.0 / 40)


@pytest.mark.parametrize("name", ["recovery.helpers_per_obj",
                                  "ec.slot_order_ms_per_op"])
def test_a_reader_with_nothing_to_read_reads_nothing(name, monkeypatch):
    """An untraced run, and a program whose records lack the span or
    the tags (the parent's)."""
    from ceph_tpu.utils import tracing
    compute = harness.load_module("layer_metrics", name).compute
    assert compute(_run()) is None
    monkeypatch.setattr(tracing, "span_log", lambda since=None, until=None: [
        {"name": "recovery.launch", "start": 105.0, "dur": 0.01,
         "self": 0.01, "nbytes": 16 * 1_572_864}])
    assert compute(_run(trace={"busy_s": 0.1})) is None
