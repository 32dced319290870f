"""The `rbd_bench` driver at a size a test run can hold, on the CPU: k=2
m=1, 32 KiB objects (four stripes of two 4 KiB chunks), an image of 8
objects on four OSDs and two PGs, 4 loops, a window of a second or two.
Same files, same driver, same comparison as the cell's. The EC backend's
host shortcut for the CPU backend is switched off, as in
`test_drivers_cpu.py`, so that the fused delta program runs (on XLA's CPU
backend).

A sound run comes out correct; `work_bytes` is the hand count; every
control put in the program's stead, and a fault underneath, comes out not
correct; the reference image's ordering rule holds on a hand-made history
with a racing pair; the four readers read a recorded span table, and
nothing from an empty run; a program without the counter is refused at
once."""

import copy

import numpy as np
import pytest

import rbd_controls
from bench import run as harness
from bench.reference import block_image
from tiny import (CPU_DEVICE, MANIFEST, PEAKS, SEED, cell_files, failed,
                  json_line)

NAME = "rbd_randwrite_4k_t16"
OBJECT, OBJECTS = 32768, 8


def tiny_cell():
    cell, workload, config, driver = cell_files(NAME)
    config = copy.deepcopy(config)
    config["profile"] = "plugin=jerasure technique=reed_sol_van k=2 m=1"
    config["geometry"].update(k=2, m=1, object_bytes=OBJECT,
                              shard_row_bytes=OBJECT // 2)
    config["cluster"].update(n_osds=4, pg_num=2)
    config["image"].update(size_bytes=OBJECTS * OBJECT, object_bytes=OBJECT,
                           objects=OBJECTS, blocks=OBJECTS * OBJECT // 4096)
    workload = dict(workload, loops=4, distinct_payloads=4, warm_min_s=0.5,
                    warm_quiet_s=0.3)
    return cell, workload, config, driver


def run_tiny(driver=None, seconds=1.5):
    cell, workload, config, fresh = tiny_cell()
    return harness.run_cell(MANIFEST, cell, workload, config,
                            driver or fresh, CPU_DEVICE, PEAKS, SEED, seconds,
                            trace=False)


@pytest.fixture
def device_path(monkeypatch):
    from ceph_tpu.osd import ecbackend
    monkeypatch.setattr(ecbackend, "_host_crc_available", lambda: False)


@pytest.fixture(scope="module")
def observed():
    """One tiny run's `observe`, for the controls to be built from."""
    from ceph_tpu.osd import ecbackend
    _, workload, config, driver = tiny_cell()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        state = driver.setup(config, workload, SEED, print)
        try:
            driver.warm(state, print)
            run = driver.window(state, 1.0, lambda: None, print)
            ob = driver.observe(state, run)
        finally:
            driver.close(state, print)
    return workload, config, driver, ob


# -- a sound run ----------------------------------------------------------

def test_sound_run_is_correct(device_path):
    result, checks = run_tiny()
    assert failed(checks) == set()
    line = json_line(result)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"client_mb_s", "op_p95_ms", "setup_s"}
    assert list(line)[-1] == "compared"
    compared, notes = line["compared"], line["notes"]
    # all of the image is compared, not a sample: 8 objects, 24 rows
    assert compared["objects_compared"]["value"] == OBJECTS
    assert compared["rows_compared"]["value"] == OBJECTS * 3
    # every timed write took the delta path, on the device: one data
    # shard and one parity shard moved
    assert compared["rmw_ops"]["value"] >= line["attempted"] - 8
    assert compared["rmw_shard_ios_off_1_plus_m"]["value"] == 0
    assert compared["rmw_host_delta_launches"]["value"] == 0
    assert compared["journal_watermarks"]["value"] >= 2
    # the warm-up's writes and the window's are the image's history
    assert notes["writes_in_history"] >= line["attempted"] + 4
    assert notes["racing_blocks"] == compared["racing_blocks"]["value"]
    assert notes["boots"] == 1 and notes["span_log_dropped"] == 0
    assert notes["ops_first_s"][1] > 0 and notes["ops_last_s"][1] > 0
    assert len(notes["compare_s"]) == 2


def test_a_delta_computed_on_the_host_is_not_correct():
    """As the program runs on the CPU backend: the native host codec
    serves the delta, and the comparison has to refuse that."""
    result, checks = run_tiny(seconds=1.0)
    assert failed(checks) == {"rmw_host_delta_launches"}
    assert result["correct"] is False


def test_a_program_without_the_counter_is_refused_at_once(monkeypatch):
    from ceph_tpu.osd import ecbackend
    from ceph_tpu.utils.perf_counters import PerfCountersBuilder
    monkeypatch.setattr(
        ecbackend, "ec_perf_counters",
        lambda: PerfCountersBuilder("ec").add_u64_counter(
            "rmw_delta_launches", "").create_perf_counters())
    _, workload, config, driver = tiny_cell()
    with pytest.raises(SystemExit, match="rmw_host_delta_launches"):
        driver.setup(config, workload, SEED, print)


def test_another_op_or_size_is_refused():
    _, workload, config, driver = tiny_cell()
    with pytest.raises(SystemExit, match="another cell"):
        driver.setup(config, dict(workload, io_size=8192), SEED, print)


# -- shapes and files -------------------------------------------------------

def test_work_bytes_from_shapes():
    _, workload, config, driver = cell_files(NAME)
    # one 4 KiB delta row in, three parity delta rows out, four crc words
    assert driver.work_bytes(config, workload, 1) == 4 * 4096 + 4 * 4 == 16_400
    assert driver.work_bytes(config, workload, 600) == 600 * 16_400
    assert driver.work_bytes(config, workload, 0) == 0
    _, tiny_workload, tiny_config, _ = tiny_cell()
    assert driver.work_bytes(tiny_config, tiny_workload, 2) == 2 * (8192 + 8)


def test_the_committed_file_states_the_deployment():
    _, workload, config, driver = cell_files(NAME)
    pool = cell_files("rados_write_4m_t16")[2]
    for key in ("profile", "cluster"):
        assert config[key] == pool[key], key
    assert dict(config["store_as_found"], note="") \
        == dict(pool["store_as_found"], note="")
    g, image = config["geometry"], config["image"]
    assert g == dict(pool["geometry"], stripe_unit_bytes=4096)
    assert image["objects"] * image["object_bytes"] == image["size_bytes"] \
        == 256 << 20
    assert image["object_bytes"] == g["object_bytes"] == 1 << image["order"]
    assert image["blocks"] * image["block_bytes"] == image["size_bytes"]
    assert workload["io_size"] == image["block_bytes"] == 4096
    assert workload["loops"] == 16 and workload["op"] == "write_at"
    assert len(config["guarantees"]) == 6
    assert driver.object_name(config, 26) \
        == "rbd_data.10226b8b4567.000000000000001a"
    # an aligned block lies on one data column of one stripe
    assert [driver.column_of(config, off)
            for off in (0, 4096, 28672, 32768, 4190208)] == [0, 1, 7, 0, 7]


# -- the reference's ordering rule -----------------------------------------

def _write(obj, offset, payload, cut, start, end, ok=True):
    return {"object": obj, "offset": offset, "payload": payload, "cut": cut,
            "start": start, "end": end, "ok": ok}


def test_the_ordering_rule_on_a_history_with_a_racing_pair():
    rng = np.random.default_rng(31)
    payloads = [rng.integers(0, 256, 16384, dtype=np.uint8).tobytes()
                for _ in range(3)]
    block = 4096

    def cut(p, c):
        return np.frombuffer(payloads[p], np.uint8, block, c)
    history = [
        # block (0, 0): two writes one after the other: the later stands
        _write(0, 0, 1, 0, 1.0, 2.0), _write(0, 0, 2, 4096, 2.5, 3.0),
        # block (0, 4096): acknowledged out of issue order, in flight
        # together: the later acknowledgement stands, either is admissible
        _write(0, 4096, 1, 8192, 1.0, 4.0), _write(0, 4096, 2, 0, 2.0, 3.0),
        # block (1, 0): three writes; the first was acknowledged before the
        # last was issued, so only the second races with the last
        _write(1, 0, 0, 0, 1.0, 2.0), _write(1, 0, 1, 4096, 1.5, 5.0),
        _write(1, 0, 2, 8192, 3.0, 4.0),
        # a write that was not acknowledged is not applied
        _write(1, 4096, 2, 0, 1.0, 2.0, ok=False)]
    image = block_image.filled(payloads, [0, 0])
    assert image.shape == (2, 16384)
    racing = block_image.replay(image, payloads, history, block)
    assert np.array_equal(image[0, :block], cut(2, 4096))
    assert np.array_equal(image[0, block:2 * block], cut(1, 8192))
    assert np.array_equal(image[1, :block], cut(1, 4096))
    assert np.array_equal(image[1, block:],                     # the fill
                          np.frombuffer(payloads[0], np.uint8)[block:])
    assert set(racing) == {(0, 4096), (1, 0)}
    assert [np.array_equal(racing[0, 4096][0], cut(2, 0)),
            len(racing[0, 4096]), len(racing[1, 0])] == [True, 1, 1]
    assert np.array_equal(racing[1, 0][0], cut(2, 8192))
    # a device that applied the racing pair the other way round: what is
    # read back is admissible, and the image takes it
    back = image.copy()
    back[0, block:2 * block] = cut(2, 0)
    assert block_image.blocks_differing(image, back, block) == 1
    assert block_image.settle(image, racing, back, block) == 1
    assert block_image.blocks_differing(image, back, block) == 0
    # the write acknowledged first of three is not admissible
    back[1, :block] = cut(0, 0)
    assert block_image.settle(image, racing, back, block) == 0
    assert block_image.blocks_differing(image, back, block) == 1


# -- controls: in the program's stead, one guarantee broken -----------------

CONTROLS = {
    "acked_write_dropped": {"image_blocks_wrong", "stored_data_rows_wrong",
                            "stored_parity_rows_wrong", "stored_crcs_wrong"},
    "parity_left_stale": {"stored_parity_rows_wrong", "stored_crcs_wrong"},
    "stale_hinfo_crc": {"stored_crcs_wrong"},
    "one_write_full_path": {"rmw_full_fallbacks"},
    "journal_intent_left": {"journal_intents_left"},
    "_sound_reference": set(),
}


def test_the_sound_observation_passes(observed):
    workload, config, driver, ob = observed
    assert failed(driver.compare(config, workload, ob)) == set()


@pytest.mark.parametrize("what", sorted(CONTROLS))
def test_control_reads_not_correct(observed, what):
    workload, config, driver, ob = observed
    made = rbd_controls.rbd_controls(config, driver, ob)
    assert set(made) == set(CONTROLS)
    assert failed(driver.compare(config, workload, made[what])) \
        == CONTROLS[what]


def test_a_pool_that_lost_an_osd_or_an_op_is_not_correct(observed):
    workload, config, driver, ob = observed
    hurt = dict(ob, failed=1, pool=dict(ob["pool"], down=[3],
                                        suspected=[[0, 3]], pgs_recovering=1),
                counters=dict(ob["counters"], recover_launches=2))
    assert failed(driver.compare(config, workload, hurt)) == {
        "ops_failed", "osds_down_at_close", "osds_suspected_at_close",
        "pgs_recovering", "recover_launches"}


# -- a fault: the timed path broken underneath --------------------------------

def test_a_delta_program_that_bends_a_parity_byte_is_not_correct(
        device_path, monkeypatch):
    from ceph_tpu.osd.ecbackend import ECBackend
    real = ECBackend._delta_parity_crcs

    def altered(self, touched, deltas):
        parity, crcs = real(self, touched, deltas)
        bent = np.array(parity)
        bent[0, 0, -1] ^= 1
        return bent, crcs
    monkeypatch.setattr(ECBackend, "_delta_parity_crcs", altered)
    result, checks = run_tiny(seconds=1.0)
    assert failed(checks) == {"stored_parity_rows_wrong"}
    assert result["correct"] is False


# -- the four readers ---------------------------------------------------------

RUN = {"ops": [], "t0": 100.0, "t1": 130.0, "window_s": 30.0,
       "counters": {"ops_done": 40, "rmw_delta_launches": 30},
       "trace": {"busy_s": 0.01, "window_s": 5.0}, "traced_ops": 4,
       "set_up_seconds": 1.0, "peaks": {"hbm_bytes_per_s": 819e9},
       "notes": {"span_log_dropped": 0}}


def _rec(name, self_s, end=110.0):
    return {"name": name, "start": end - self_s, "dur": self_s,
            "self": self_s, "trace_id": None, "nbytes": None}


LOG = [_rec("ecbackend.rmw", 0.004), _rec("ecbackend.rmw.prefetch", 0.040),
       _rec("ecbackend.rmw.delta.stage", 0.001),
       _rec("ecbackend.rmw.delta.stage", 0.001),
       _rec("ecbackend.rmw.delta.launch", 0.006),
       _rec("ecbackend.rmw.delta.fetch", 0.008),
       _rec("ecbackend.rmw.journal", 0.060), _rec("ecbackend.rmw.apply", 0.080),
       _rec("ecbackend.rmw.full", 0.5),             # nobody's metric
       _rec("osd.persist_meta", 0.120), _rec("osd.op", 0.9)]
WANT = {"ec.rmw_ms_per_op": 50.0, "ec.rmw_device_wait_ms_per_op": 2.0,
        "ec.rmw_launches_per_op": 0.75, "osd.persist_meta_ms_per_op": 30.0}


@pytest.fixture
def log(monkeypatch):
    from ceph_tpu.utils import tracing
    monkeypatch.setattr(tracing, "_LOG", list(LOG))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_recorded_span_table(name, log):
    reader = harness.load_module("layer_metrics", name)
    assert reader.compute(copy.deepcopy(RUN)) == pytest.approx(WANT[name])
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [NAME]


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("lacks", ["trace", "spans", "counters", "wrapped"])
def test_reader_with_nothing_to_read_returns_nothing(name, lacks, log,
                                                     monkeypatch):
    run = copy.deepcopy(RUN)
    counted = name == "ec.rmw_launches_per_op"
    if lacks == "trace":
        run["trace"] = None
    elif lacks == "spans":          # the parent: a program without them
        from ceph_tpu.utils import tracing
        monkeypatch.setattr(tracing, "_LOG", [_rec("osd.op", 0.9)])
    elif lacks == "counters":
        run["counters"] = {}
    else:                           # the log wrapped: short of its oldest
        run["notes"]["span_log_dropped"] = 7
    got = harness.load_module("layer_metrics", name).compute(run)
    if counted == (lacks == "counters"):
        assert got is None
    else:
        assert got == pytest.approx(WANT[name])
