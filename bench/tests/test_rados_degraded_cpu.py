"""The `rados_degraded` driver at `tiny.py`'s size on the CPU: k=2 m=1,
8 KiB objects, four OSDs of which one is stopped and marked down in
set-up and stays in. A sound run comes out correct; its `work_bytes` are
the hand count; the controls put in the program's stead (a read served
from a stale object; a pool that recovered during the window) and a
fault underneath (a decode that rebuilds a wrong byte) come out not
correct; a program without the admin `down` is refused at once."""

import copy

import numpy as np
import pytest

from bench import run as harness
from tiny import (CPU_DEVICE, MANIFEST, PEAKS, SEED, cell_files, failed,
                  json_line, tiny)

NAME = "rados_read_degraded_4m_t16"
# the tiny map, as CRUSH lays 4 PGs on 4 OSDs: osd.1 is no PG's primary
# and holds data slot 1 of PGs 0 and 2; the 8 names fall 4 on those
TINY_MAP = {"pgs_data_slot_lost": 2, "pgs_parity_slot_lost": 0,
            "pgs_untouched": 2, "rebuilding_share": 0.5}


def tiny_cell():
    cell, workload, config, driver = tiny(NAME)
    config["failure"].update(TINY_MAP)
    return cell, workload, config, driver


def run_tiny(driver=None, config=None, seconds=1.5):
    cell, workload, tiny_config, fresh = tiny_cell()
    return harness.run_cell(MANIFEST, cell, workload, config or tiny_config,
                            driver or fresh, CPU_DEVICE, PEAKS, SEED, seconds,
                            trace=False)


@pytest.fixture(scope="module")
def observed():
    """One tiny run's `observe`, for the controls to be built from."""
    _, workload, config, driver = tiny_cell()
    state = driver.setup(config, workload, SEED, print)
    try:
        driver.warm(state, print)
        run = driver.window(state, 1.0, lambda: None, print)
        ob = driver.observe(state, run)
    finally:
        driver.close(state, print)
    return workload, config, driver, ob


def test_sound_run_is_correct():
    result, checks = run_tiny()
    assert failed(checks) == set()
    line = json_line(result)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"client_mb_s", "op_p95_ms", "setup_s"}
    assert list(line)[-1] == "compared"
    compared, notes = line["compared"], line["notes"]
    assert notes["failure"]["victim"] == 1 and notes["boots"] == 1
    assert {k: notes["failure"][k] for k in TINY_MAP} == TINY_MAP
    # every object and every timed read is compared; the reads of the
    # two PGs that lost a data slot were rebuilt, on the device
    assert compared["objects_compared"]["value"] == 8
    assert compared["timed_reads_compared"]["value"] >= line["attempted"]
    assert compared["degraded_reads"]["value"] \
        >= compared["degraded_reads"]["limit"] >= 1
    assert compared["rs_decode_sample_reads"]["value"] >= 5
    assert compared["host_decode_launches"]["value"] == 0


def test_the_files_map_constants_are_held_to_the_map_of_the_run():
    """The file as committed states the 12-OSD map; on the tiny map it is
    another pool, and only those four numbers say so."""
    _, _, config, _ = tiny(NAME)
    result, checks = run_tiny(config=config)
    assert failed(checks) - {"rebuilt_rows_off_share"} == {
        key + "_off_file" for key in TINY_MAP} - {"pgs_parity_slot_lost_off_file"}
    assert result["correct"] is False


def test_work_bytes_from_shapes_and_the_files_constant():
    _, workload, config, driver = cell_files(NAME)
    # every read: 8 rows of 512 KiB through the crc; a rebuilding read
    # (all of them: rebuilding_share 1.0) 8 rows into the decode, 1 out
    assert config["failure"]["rebuilding_share"] == 1.0
    assert driver.work_bytes(config, workload, 1) \
        == 4_194_304 + 4_718_592 == 8_912_896
    assert driver.work_bytes(config, workload, 75) == 75 * 8_912_896
    assert driver.work_bytes(config, workload, 0) == 0
    half = copy.deepcopy(config)
    half["failure"]["rebuilding_share"] = 0.5
    assert driver.work_bytes(half, workload, 2) == 2 * 4_194_304 + 4_718_592


def test_the_committed_file_states_the_deployment():
    _, workload, config, _ = cell_files(NAME)
    seq = cell_files("rados_seq_4m_t16")
    for key in ("profile", "geometry", "cluster", "store_as_found"):
        assert config[key] == seq[2][key], key
    for key in ("op", "loops", "distinct_payloads", "working_set_objects",
                "readback_objects", "trace_after_s", "trace_seconds"):
        assert workload[key] == seq[1][key], key
    failure = config["failure"]
    assert failure["mon_osd_down_out_interval_s"] == 600
    assert failure["rebuilding_share"] >= 0.5
    assert failure["pgs_data_slot_lost"] + failure["pgs_parity_slot_lost"] \
        + failure["pgs_untouched"] == config["cluster"]["pg_num"]


def test_victim_rule():
    from bench.drivers import rados_degraded as drv
    acting = {0: [2, 1, 3], 1: [0, 3, 2], 2: [2, 1, 3], 3: [2, 3, 0]}
    pgs = [0, 1, 2, 3, 0, 1, 2, 3]
    assert drv.choose_victim(acting, 2, pgs, [0, 1, 2, 3]) == 1
    # ties go to the lowest id; a primary is never chosen
    assert drv.choose_victim({0: [3, 1, 2], 1: [3, 2, 1]}, 2, [0, 1],
                             [0, 1, 2, 3]) == 1
    with pytest.raises(RuntimeError):
        drv.choose_victim({0: [0, 1, 2], 1: [1, 2, 0], 2: [2, 0, 1]}, 2,
                          [0, 1, 2], [0, 1, 2])
    got = drv.map_constants(acting, 2, pgs, 1)
    assert {k: got[k] for k in TINY_MAP} == TINY_MAP
    assert got["slot_lost"] == {0: 1, 1: None, 2: 1, 3: None}


def test_a_program_without_the_admin_down_is_refused_at_once(monkeypatch):
    from ceph_tpu.osd.standalone import Client
    monkeypatch.delattr(Client, "osd_down")
    _, workload, config, driver = tiny_cell()
    with pytest.raises(SystemExit, match="osd down"):
        driver.setup(config, workload, SEED, print)


# -- controls: in the program's stead, one guarantee broken -------------

def test_the_sound_observation_passes(observed):
    workload, config, driver, ob = observed
    assert failed(driver.compare(config, workload, ob)) == set()


def test_a_read_served_from_a_stale_object_is_not_correct(observed):
    """Every read of one object returns the bytes of the write before:
    another payload under the same name."""
    workload, config, driver, ob = observed
    victim = ob["reads"][0]["name"]
    older = ob["payloads"][(ob["reads"][0]["payload"] + 1)
                           % len(ob["payloads"])]
    stale = dict(ob, reads=[dict(r, returned=older) if r["name"] == victim
                            else r for r in ob["reads"]])
    assert "timed_reads_wrong" in failed(driver.compare(config, workload,
                                                        stale))
    # and where that object is in the sample, the plain decode of what
    # the survivors store says so too
    sampled = {o["name"] for o in ob["objects"] if "readback" in o}
    everywhere = dict(ob, reads=[
        dict(r, returned=ob["payloads"][(r["payload"] + 1)
                                        % len(ob["payloads"])])
        for r in ob["reads"]])
    assert sampled
    assert {"timed_reads_wrong", "rs_decode_sample_wrong"} <= failed(
        driver.compare(config, workload, everywhere))


def test_a_pool_that_recovered_during_the_window_is_not_correct(observed):
    """The victim went out, the spare took its slots and recovery
    rebuilt them: reads stop rebuilding part of the way through."""
    workload, config, driver, ob = observed
    n = len(ob["objects"])
    healed = dict(
        ob,
        pool=dict(ob["pool"], victim_in=False, slots_repointed=2,
                  pgs_recovering=0),
        counters=dict(ob["counters"], recover_launches=2,
                      recovered_objects=n // 2, degraded_reads=1,
                      decode_rows_rebuilt=1))
    bad = failed(driver.compare(config, workload, healed))
    assert {"victim_in_at_close", "slots_repointed", "recover_launches",
            "recovered_objects", "degraded_reads"} <= bad
    # recovery still running at the close, or a second OSD lost
    assert "pgs_recovering" in failed(driver.compare(
        config, workload, dict(ob, pool=dict(ob["pool"], pgs_recovering=1))))
    assert {"osds_down_at_close", "victim_down_at_close"} <= failed(
        driver.compare(config, workload, dict(ob, pool=dict(
            ob["pool"], down=ob["pool"]["down"] + [0]))))


def test_rows_rebuilt_on_the_host_are_not_correct(observed):
    workload, config, driver, ob = observed
    host = dict(ob, counters=dict(ob["counters"], host_decode_launches=3))
    assert failed(driver.compare(config, workload, host)) == {
        "host_decode_launches"}


# -- a fault: the timed path broken underneath ----------------------------

def test_a_decode_that_rebuilds_a_wrong_byte_is_not_correct(monkeypatch):
    from ceph_tpu.osd.ecbackend import ECBackend
    real = ECBackend._decode_rows

    def altered(self, want, rows, sl):
        out = real(self, want, rows, sl)
        lost = [s for s in want if s not in rows]
        if lost:
            bent = np.array(out[lost[0]])
            bent[..., -1] ^= 1
            out = {**out, lost[0]: bent}
        return out
    monkeypatch.setattr(ECBackend, "_decode_rows", altered)
    result, checks = run_tiny()
    assert {"timed_reads_wrong", "readback_wrong",
            "rs_decode_sample_wrong"} <= failed(checks)
    assert result["correct"] is False
